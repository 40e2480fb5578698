"""Optimizer construction (optax) with the standard LLM fine-tune recipe.

AdamW + linear warmup + cosine decay + global-norm clipping. Kept as plain
optax so the optimizer state is a pytree that shards with the same FSDP rules
as the params (runbooks_tpu.parallel.sharding).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import optax


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    learning_rate: float = 2e-5
    warmup_steps: int = 100
    total_steps: int = 1000
    schedule: str = "cosine"          # "cosine" | "linear" | "constant"
    min_lr_ratio: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip_norm: Optional[float] = 1.0
    # Dtype for the adam first moment (mu). None keeps the param dtype
    # (f32 masters -> f32 mu). "bfloat16" halves mu bytes — f32 masters +
    # moments are what forces full remat (ROADMAP S8); bf16 mu is the first
    # of the three state-memory levers (mu dtype, param dtype, state
    # sharding).
    mu_dtype: Optional[str] = None


def make_schedule(cfg: OptimizerConfig) -> optax.Schedule:
    warmup = optax.linear_schedule(0.0, cfg.learning_rate,
                                   max(cfg.warmup_steps, 1))
    decay_steps = max(cfg.total_steps - cfg.warmup_steps, 1)
    if cfg.schedule == "cosine":
        decay = optax.cosine_decay_schedule(
            cfg.learning_rate, decay_steps, alpha=cfg.min_lr_ratio)
    elif cfg.schedule == "linear":
        decay = optax.linear_schedule(
            cfg.learning_rate, cfg.learning_rate * cfg.min_lr_ratio, decay_steps)
    else:
        decay = optax.constant_schedule(cfg.learning_rate)
    return optax.join_schedules([warmup, decay], [cfg.warmup_steps])


def make_optimizer(cfg: OptimizerConfig) -> optax.GradientTransformation:
    chain = []
    if cfg.grad_clip_norm is not None:
        chain.append(optax.clip_by_global_norm(cfg.grad_clip_norm))
    chain.append(
        optax.adamw(
            learning_rate=make_schedule(cfg),
            b1=cfg.b1, b2=cfg.b2, eps=cfg.eps,
            weight_decay=cfg.weight_decay,
            mu_dtype=cfg.mu_dtype,
        )
    )
    return optax.chain(*chain)
