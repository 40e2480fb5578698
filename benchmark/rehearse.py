#!/usr/bin/env python3
"""Rehearsal without the chip: compile, for the described `v5e:2x2`, every
program each cell warms at its real size, and print `memory_analysis()`.

  JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [--workload NAME ...]

The TPU's compiler is installed in the sandbox and compiles for a chip that
is described and not attached, so what it refuses here (out of HBM, a
kernel it cannot lower or partition) costs no chip time. It is the
evidence for the chat server's slots and the 40B depth. Nothing runs:
this gives sizes, never a time, and is not a chip run.

The program's own builders are called (serve/engine.make_prefill_fn,
make_decode_fn, train/lora.make_lora_train_step) with shapes only; the
`is this a TPU` probe is forced to say yes so that they take the branch
they take on the chip (the verify skill's recipe).
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("RBT_JAX_CACHE", "0")

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, ROOT)

from benchlib import spec  # noqa: E402

GIB = 1 << 30


def force_tpu_branches():
    import runbooks_tpu.models.transformer as tr
    import runbooks_tpu.ops.flash_attention as fa
    import runbooks_tpu.serve.engine as eng
    import runbooks_tpu.utils.hw as hw

    hw.on_tpu = lambda: True
    for mod in (tr, fa, eng):
        if hasattr(mod, "on_tpu"):
            mod.on_tpu = hw.on_tpu


def report(label, compiled, t0):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    print(f"  {label:<34} args {m.argument_size_in_bytes / GIB:6.2f} GiB  "
          f"temps {m.temp_size_in_bytes / GIB:6.2f} GiB  total "
          f"{total / GIB:6.2f} GiB a device  ({time.time() - t0:.0f} s)",
          flush=True)
    return total


def rehearse(cell: spec.Cell, topo) -> float:
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import (
        KVCache,
        init_params,
        param_logical_axes,
    )
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh
    from runbooks_tpu.parallel.sharding import spec_for_array, tree_shardings
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_config(cell.config["model"], **cell.config["model_overrides"])
    entry = cell.traffic["entry"]
    mesh_args = {k[len("mesh_"):]: v
                 for k, v in cell.config["mesh_params"][entry].items()}
    devices = topo.devices[:cell.chips]
    mesh = make_mesh(MeshConfig(**mesh_args) if mesh_args
                     else MeshConfig(fsdp=1), devices=devices)
    rep = NamedSharding(mesh, P())
    key = jax.random.key(0)
    shapes = jax.eval_shape(functools.partial(init_params, cfg), key)
    p_sh = tree_shardings(shapes, param_logical_axes(cfg), mesh)

    def with_sh(tree, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings)

    params = with_sh(shapes, p_sh)
    sds = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=rep)  # noqa
    i32 = functools.partial(sds, jnp.int32)
    f32 = functools.partial(sds, jnp.float32)
    key_s = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)
    worst = 0.0
    print(f"{cell.name}: {cfg.name} x {cell.traffic_name}, "
          f"{cfg.num_layers} layers, {cell.chips} chip(s), mesh "
          f"{dict(mesh.shape)}", flush=True)
    with jax.set_mesh(mesh):
        if entry == "serve":
            from runbooks_tpu.serve.engine import (
                _buckets,
                make_decode_fn,
                make_prefill_fn,
                view_buckets_for,
            )

            sp = cell.traffic["server_params"]
            slots, max_len = int(sp["max_slots"]), int(sp["max_seq_len"])
            pool = jax.eval_shape(lambda: KVCache.create(
                cfg, slots, max_len, trash_slot=True, quantize_kv=False))

            def cache_sh(a):
                logical = (None, "batch", None, "act_heads", None)[:a.ndim]
                return NamedSharding(mesh, spec_for_array(
                    a.shape, logical, mesh)) if a.ndim else rep

            pool = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=cache_sh(a)), pool)
            prefill = jax.jit(make_prefill_fn(cfg, max_len + 1),
                              donate_argnums=(1,))
            for bucket in _buckets(max_len):
                for rows in (1, slots):
                    t0 = time.time()
                    c = prefill.lower(
                        params, pool, i32(rows, bucket), i32(rows, bucket),
                        i32(rows), i32(rows), key_s, f32(rows), i32(rows),
                        f32(rows)).compile()
                    worst = max(worst, report(
                        f"prefill [{rows}, {bucket}]", c, t0))
            for view in view_buckets_for(max_len):
                t0 = time.time()
                dec = jax.jit(make_decode_fn(cfg, 8, max_len, max_len, view),
                              donate_argnums=(1,))
                c = dec.lower(
                    params, pool, i32(slots), i32(slots), key_s, f32(slots),
                    i32(slots), f32(slots), i32(slots), i32(slots),
                    sds(jnp.bool_, slots)).compile()
                worst = max(worst, report(f"decode view {view} x8 steps",
                                          c, t0))
        else:
            from runbooks_tpu.train.lora import (
                LoraConfig,
                make_lora_train_step,
            )
            from runbooks_tpu.train.optimizer import (
                OptimizerConfig,
                make_optimizer,
            )

            job = cell.traffic["job_params"]
            opt_keys = OptimizerConfig.__dataclass_fields__
            opt = make_optimizer(OptimizerConfig(
                **{k: v for k, v in job.items() if k in opt_keys}))
            lcfg = LoraConfig(**job["lora"])
            state, sh = create_lora_train_state_shapes(
                cfg, lcfg, shapes, opt, mesh, key)
            state = with_sh(state, sh)
            step = make_lora_train_step(cfg, lcfg, opt, mesh, sh, p_sh)
            b, s = int(job["batch_size"]), int(job["seq_len"])
            batch = {"tokens": i32(b, s), "targets": i32(b, s),
                     "segment_ids": i32(b, s), "positions": i32(b, s),
                     "loss_mask": f32(b, s)}
            t0 = time.time()
            c = step.lower(state, params, batch).compile()
            worst = max(worst, report(f"LoRA step [{b}, {s}]", c, t0))
    return worst


def create_lora_train_state_shapes(cfg, lcfg, base_shapes, opt, mesh, key):
    """State shapes and shardings without materialising anything."""
    import jax
    import jax.numpy as jnp

    from runbooks_tpu.train.lora import init_lora, lora_logical_axes
    from runbooks_tpu.train.step import TrainState, infer_state_shardings

    def init_fn(rng):
        lora = init_lora(base_shapes, lcfg, rng)
        return TrainState(step=jnp.zeros((), jnp.int32), params=lora,
                          opt_state=opt.init(lora))

    shapes = jax.eval_shape(init_fn, key)
    axes = lora_logical_axes(lcfg, shapes.params)
    return shapes, infer_state_shardings(axes, shapes, mesh, None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append",
                    help="cells to rehearse (default: every cell)")
    args = ap.parse_args(argv)
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    force_tpu_branches()
    bench = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = args.workload or [w["name"] for w in bench["workloads"]]
    hbm = 15.75
    for name in names:
        worst = rehearse(spec.load_cell(name), topo)
        print(f"{name}: largest program {worst / GIB:.2f} GiB a device of "
              f"{hbm} GiB usable", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
