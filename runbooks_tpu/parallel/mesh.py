"""Device-mesh construction for DP/FSDP/SP/TP (and later EP) parallelism.

The reference has no parallelism engine at all — its scaling story is
"resources.gpu.count on a single pod" (reference: internal/resources/
resources.go:39-65, SURVEY.md §2a). Here the mesh is the core scaling
primitive: every workload (train or serve) runs under one
``jax.sharding.Mesh`` whose axes are, outermost to innermost:

  data      — pure data parallelism (gradients all-reduced over DCN ok)
  fsdp      — data parallelism with parameter/optimizer sharding (ZeRO-3);
              collectives should ride ICI
  sequence  — context/sequence parallelism for long sequences (ring attention)
  tensor    — megatron-style tensor parallelism (innermost = fastest ICI)

Axis order matters on TPU: jax.make_mesh assigns the innermost mesh axes to
the most tightly-coupled physical neighbors, so tensor-parallel collectives
(per-layer all-reduces) get the best links, while pure-DP gradient reductions
can span slices over DCN.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh

MESH_AXES = ("data", "stage", "expert", "fsdp", "sequence", "tensor")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Parallelism degrees. Use -1 for at most one axis to mean "fill with
    whatever devices remain" (like the reference's implicit single-axis
    gpu.count, but over a real mesh).

    stage  — pipeline parallelism (parallel/pipeline.py): the stacked-layer
             leading dim shards over stages; activations flow stage->stage
             via ppermute. Cross-stage traffic is one activation tensor per
             microbatch tick, so the stage axis sits outermost after data
             (it tolerates the slowest links — even DCN).
    expert — expert parallelism for MoE layers (models/moe.py): the expert
             leading dim shards over this axis; tokens route via all-to-all.
    """

    data: int = 1
    stage: int = 1
    expert: int = 1
    fsdp: int = -1
    sequence: int = 1
    tensor: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        sizes = {a: getattr(self, a) for a in MESH_AXES}
        fill = [a for a, s in sizes.items() if s == -1]
        if len(fill) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {fill}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if fill:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[fill[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices but {n_devices} available"
            )
        return MeshConfig(**sizes)


def make_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    config = (config or MeshConfig()).resolve(len(devices))
    shape = tuple(getattr(config, a) for a in MESH_AXES)
    # Auto axis types = classic GSPMD: XLA propagates shardings from the
    # in/out_shardings + with_sharding_constraint hints. (The default under
    # jax.set_mesh is the explicit sharding-in-types mode, which would
    # require out_sharding annotations on every gather/einsum.)
    return jax.make_mesh(shape, MESH_AXES, devices=devices,
                         axis_types=(AxisType.Auto,) * len(MESH_AXES))


def single_device_mesh() -> Mesh:
    """A 1x1x1x1 mesh over the first device — lets jit'ed sharded code run
    unchanged on one chip (all PartitionSpecs collapse to replicated)."""
    return make_mesh(MeshConfig(data=1, fsdp=1, sequence=1, tensor=1),
                     devices=jax.devices()[:1])
