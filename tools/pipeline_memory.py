"""gpipe-vs-1F1B activation-memory comparison on the virtual CPU mesh.

The 1F1B claim: in-flight activations are O(stages) regardless of
microbatch count (residual ring of min(M, 2S-1) block inputs), while the
gpipe/autodiff schedule keeps O(M) microbatch activations live. CPU
``memory_analysis()`` cannot model cross-tick buffer reuse exactly, but the
M-scaling DIRECTION is visible in temp bytes: gpipe temp should grow with
M, 1F1B should stay ~flat.

Usage: python tools/pipeline_memory.py [--stages 4] [--layers 8]
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from runbooks_tpu.models.config import get_config  # noqa: E402
from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh  # noqa: E402
from runbooks_tpu.train.optimizer import OptimizerConfig, make_optimizer  # noqa: E402
from runbooks_tpu.train.step import create_train_state, make_train_step  # noqa: E402


def measure(schedule, M, stages, layers, bs, seq):
    cfg = dataclasses.replace(
        get_config("debug"), vocab_size=512, hidden_size=128,
        intermediate_size=256, num_layers=layers, num_heads=8,
        num_kv_heads=8, head_dim=16, max_seq_len=seq, dtype="float32",
        pipeline_schedule=schedule, pipeline_microbatches=M,
        remat_policy="none")
    devices = jax.devices("cpu")
    if len(devices) < stages:
        raise SystemExit(
            f"need {stages} CPU devices, have {len(devices)}: run with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={stages}")
    mesh = make_mesh(MeshConfig(data=1, fsdp=1, sequence=1, tensor=1,
                                stage=stages), devices=devices[:stages])
    opt = make_optimizer(OptimizerConfig(total_steps=100, warmup_steps=0))
    state, shardings = create_train_state(cfg, opt, mesh, jax.random.key(0))
    step = make_train_step(cfg, opt, mesh, shardings)
    batch = {
        "tokens": jnp.zeros((bs, seq), jnp.int32),
        "targets": jnp.zeros((bs, seq), jnp.int32),
        "loss_mask": jnp.ones((bs, seq), jnp.float32),
    }
    with jax.set_mesh(mesh):
        mem = step.lower(state, batch).compile().memory_analysis()
    return mem.temp_size_in_bytes / 2**20


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()
    S = args.stages
    bs = 8 * S
    print(f"# S={S} L={args.layers} seq={args.seq}, batch FIXED at {bs}: "
          "1F1B's in-flight set is ring_slots x (b/M) and must SHRINK as M "
          "grows; gpipe's autodiff tape is O(batch x layers) regardless. "
          "remat none, virtual CPU mesh.")
    print(f"{'schedule':10}{'M':>4}{'temp MiB':>10}")
    for schedule in ("gpipe", "1f1b"):
        for M in (S, 2 * S, 4 * S):
            t = measure(schedule, M, S, args.layers, bs, args.seq)
            print(f"{schedule:10}{M:>4}{t:>10.1f}")


if __name__ == "__main__":
    main()
