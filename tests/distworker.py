"""Worker process for the multi-process jax.distributed test.

Launched by tests/test_distributed.py with the exact env the operator's
fan-out injects (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
JAX_PROCESS_ID — cloud/resources.py:distributed_env). Forms the runtime via
parallel.distributed.initialize(), then proves the collectives work:

1. pmap psum across all processes' devices;
2. a global-mesh jit train step on a tiny model, with the batch assembled
   from per-process shards (the real multi-host input path).

Prints one JSON line for the parent to assert on.
"""

import json
import os
import sys

# Launched as `python tests/distworker.py`: the repo root (not tests/) is
# what imports must resolve against.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from runbooks_tpu.parallel.distributed import (  # noqa: E402
    initialize,
    is_primary,
    process_index,
)


def main() -> int:
    formed = initialize(timeout_s=60)
    assert formed, "initialize() returned False with slice env set"
    nproc = int(os.environ["JAX_NUM_PROCESSES"])
    assert jax.process_count() == nproc, (
        jax.process_count(), nproc)
    assert jax.process_index() == process_index()

    # 1. Cross-process psum: every local device contributes 1.
    local = jax.local_device_count()
    total = jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")(
        jnp.ones((local,)))
    world = int(np.asarray(total)[0])
    assert world == jax.device_count(), (world, jax.device_count())

    # 2. One train step over a global data-parallel mesh.
    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh
    from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer
    from runbooks_tpu.train.step import create_train_state, make_train_step

    cfg = get_config("debug", vocab_size=64, hidden_size=32,
                     intermediate_size=64, num_layers=1, num_heads=4,
                     num_kv_heads=4, head_dim=8, max_seq_len=16,
                     dtype="float32")
    mesh = make_mesh(MeshConfig(data=jax.device_count()))
    opt = make_optimizer(OptimizerConfig(total_steps=2, warmup_steps=0))
    state, shardings = create_train_state(cfg, opt, mesh, jax.random.key(0))
    step = make_train_step(cfg, opt, mesh, shardings)

    # Per-process local shard -> global array (the multi-host input path).
    from jax.sharding import NamedSharding, PartitionSpec as P

    global_bs, seq = jax.device_count(), 8
    rng = np.random.default_rng(0)  # same seed everywhere; slice per proc
    all_tokens = rng.integers(0, cfg.vocab_size,
                              (global_bs, seq + 1)).astype(np.int32)
    per = global_bs // jax.process_count()
    lo = jax.process_index() * per

    def globalize(arr):
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, P(("data",))), arr[lo:lo + per])

    batch = {
        "tokens": globalize(all_tokens[:, :-1]),
        "targets": globalize(all_tokens[:, 1:]),
        "loss_mask": globalize(
            np.ones((global_bs, seq), np.float32)),
    }
    with jax.set_mesh(mesh):
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])
    assert np.isfinite(loss), loss

    print(json.dumps({"ok": True, "process": jax.process_index(),
                      "world_devices": world, "loss": round(loss, 4),
                      "primary": is_primary()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
