"""Remat-policy x state-dtype memory frontier on the virtual CPU mesh.

Repeatable source of the memory-frontier tables: compiles the full
train step for each (remat_policy, param/mu dtype) combination and prints
``compiled.memory_analysis()`` temp + argument bytes. No TPU needed — XLA's
buffer assignment on CPU gives the relative ordering the policies will show
on hardware (absolute HBM numbers differ; validate the winner on-chip via
RBT_BENCH_REMAT / RBT_BENCH_PARAM_DTYPE / RBT_BENCH_MU_DTYPE).

Usage: python tools/memory_frontier.py [--layers 6] [--bs 8] [--seq 1024]
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


def measure(cfg, mesh, mu_dtype, bs, seq):
    opt = make_optimizer(OptimizerConfig(total_steps=1000, warmup_steps=10,
                                         mu_dtype=mu_dtype))
    state, shardings = create_train_state(cfg, opt, mesh, jax.random.key(0))
    step = make_train_step(cfg, opt, mesh, shardings)
    batch = {
        "tokens": jnp.zeros((bs, seq), jnp.int32),
        "targets": jnp.zeros((bs, seq), jnp.int32),
        "loss_mask": jnp.ones((bs, seq), jnp.float32),
    }
    with jax.set_mesh(mesh):
        mem = step.lower(state, batch).compile().memory_analysis()
    return mem


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="bench-410m")
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--bs", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    args = ap.parse_args()

    mesh = make_mesh(MeshConfig(data=1, fsdp=8, sequence=1, tensor=1))
    base = dataclasses.replace(get_config(args.model),
                               num_layers=args.layers, max_seq_len=args.seq)

    combos = [
        ("none", "float32", None),
        ("nothing_saveable", "float32", None),
        ("dots_saveable", "float32", None),
        ("save_attn_out", "float32", None),
        ("nothing_saveable", "bfloat16", "bfloat16"),
        ("save_attn_out", "bfloat16", "bfloat16"),
        ("none", "bfloat16", "bfloat16"),
    ]
    print(f"# {args.model} L={args.layers} bs{args.bs}x{args.seq} fsdp8 "
          "(virtual CPU mesh)")
    print(f"{'policy':34}{'param/mu':18}{'temp MiB':>10}{'args MiB':>10}")
    for policy, pd, mu in combos:
        cfg = dataclasses.replace(base, remat_policy=policy, param_dtype=pd)
        mem = measure(cfg, mesh, mu, args.bs, args.seq)
        t = mem.temp_size_in_bytes / 2**20
        a = mem.argument_size_in_bytes / 2**20
        print(f"{policy:34}{pd + '/' + str(mu):18}{t:10.1f}{a:10.1f}")


if __name__ == "__main__":
    main()
