"""LoRA fine-tuning (the reference's headline example is a Llama-2-7B LoRA-style
HF fine-tune — reference: examples/llama2-7b/finetuned-model.yaml; here LoRA is
a first-class, TPU-sharded implementation).

Formulation: for each target matrix W [*, in, out], learn A [*, in, r] and
B [*, r, out]; the effective weight is W + (alpha/r) * A @ B. Training merges
on the fly inside the loss (XLA fuses the small matmuls; grads flow only to
A/B), so the base params stay frozen and can even live in bf16. ``merge``
folds the deltas into the base weights for serving/export.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

Params = Any

# Matrices eligible for LoRA, by their path inside params["layers"].
DEFAULT_TARGETS = ("attn.wq", "attn.wk", "attn.wv", "attn.wo")
ALL_TARGETS = DEFAULT_TARGETS + ("mlp.wi_gate", "mlp.wi_up", "mlp.wi", "mlp.wo")


@dataclasses.dataclass(frozen=True)
class LoraConfig:
    rank: int = 8
    alpha: float = 16.0
    targets: Sequence[str] = DEFAULT_TARGETS

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


def _get(tree: Params, dotted: str):
    node = tree
    for part in dotted.split("."):
        if part not in node:
            return None
        node = node[part]
    return node


def init_lora(params: Params, cfg: LoraConfig, rng: jax.Array) -> Params:
    """LoRA params matching the model's stacked-layer layout:
    {target: {"a": [L, in, r], "b": [L, r, out]}}. A ~ N(0, 1/in), B = 0
    (standard init: delta starts at zero)."""
    lora: Dict[str, Dict[str, jax.Array]] = {}
    keys = jax.random.split(rng, len(cfg.targets))
    for key, target in zip(keys, cfg.targets):
        w = _get(params["layers"], target)
        if w is None:
            continue
        L, d_in, d_out = w.shape
        lora[target] = {
            "a": (jax.random.normal(key, (L, d_in, cfg.rank)) * d_in ** -0.5
                  ).astype(w.dtype),
            "b": jnp.zeros((L, cfg.rank, d_out), w.dtype),
        }
    if not lora:
        raise ValueError(f"no LoRA targets matched: {cfg.targets}")
    return lora


def lora_logical_axes(cfg: LoraConfig, params: Params) -> Params:
    """Logical axes for LoRA params: rank axis replicated, in/out axes follow
    the base matrix convention (embed/heads/mlp)."""
    base_axes = {
        "attn.wq": ("embed", "heads"), "attn.wk": ("embed", "kv_heads"),
        "attn.wv": ("embed", "kv_heads"), "attn.wo": ("heads", "embed"),
        "mlp.wi_gate": ("embed", "mlp"), "mlp.wi_up": ("embed", "mlp"),
        "mlp.wi": ("embed", "mlp"), "mlp.wo": ("mlp", "embed"),
    }
    axes: Dict[str, Dict[str, tuple]] = {}
    for target in params:
        in_ax, out_ax = base_axes.get(target, (None, None))
        axes[target] = {"a": (None, in_ax, None), "b": (None, None, out_ax)}
    return axes


def apply_lora(params: Params, lora: Params, cfg: LoraConfig) -> Params:
    """Base params with LoRA deltas folded in (lazily, inside jit)."""
    layers = dict(params["layers"])

    def fold(node: Params, path: Tuple[str, ...]):
        out = {}
        for k, v in node.items():
            sub_path = path + (k,)
            dotted = ".".join(sub_path)
            if isinstance(v, dict):
                out[k] = fold(v, sub_path)
            elif dotted in lora:
                ab = jnp.einsum(
                    "lir,lro->lio", lora[dotted]["a"], lora[dotted]["b"],
                    preferred_element_type=jnp.float32,
                )
                out[k] = (v.astype(jnp.float32)
                          + cfg.scale * ab).astype(v.dtype)
            else:
                out[k] = v
        return out

    new_params = dict(params)
    new_params["layers"] = fold(layers, ())
    return new_params


merge = apply_lora  # serving/export alias: returns fully-merged params


def trainable_param_count(lora: Params) -> int:
    import numpy as np

    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(lora))


# ---------------------------------------------------------------------------
# Sharded LoRA training (base frozen, only A/B in the optimizer)
# ---------------------------------------------------------------------------

def create_lora_train_state(model_cfg, lora_cfg: LoraConfig, base_params,
                            optimizer, mesh, rng, rules=None):
    """Sharded TrainState whose params are the LoRA tree only. Returns
    (state, state_shardings)."""
    import jax.numpy as jnp
    from runbooks_tpu.train.step import (
        TrainState,
        infer_state_shardings,
        layout_invariant_init,
    )

    def init_fn(rng):
        lora = init_lora(base_params, lora_cfg, rng)
        return TrainState(step=jnp.zeros((), jnp.int32), params=lora,
                          opt_state=optimizer.init(lora))

    state_shapes = jax.eval_shape(init_fn, rng)
    axes = lora_logical_axes(lora_cfg, state_shapes.params)
    shardings = infer_state_shardings(axes, state_shapes, mesh, rules)
    with jax.set_mesh(mesh), layout_invariant_init():
        state = jax.jit(init_fn, out_shardings=shardings)(rng)
    return state, shardings


def make_lora_train_step(model_cfg, lora_cfg: LoraConfig, optimizer, mesh,
                         state_shardings, base_shardings, remat: bool = True,
                         accumulate_steps: int = 1, loss_chunk: int = 0):
    """jit'ed (state, base_params, batch) -> (state, metrics); grads flow only
    to the LoRA tree, base stays frozen (and may be bf16).

    accumulate_steps/loss_chunk mirror make_train_step: k-microbatch
    gradient accumulation with an f32 accumulator, and the chunked fused
    cross-entropy that never materializes [b, s, vocab] logits (the merge
    happens per microbatch inside the differentiated graph either way)."""
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from runbooks_tpu.train.step import (
        TrainState,
        accumulated_value_and_grad,
        make_ce_terms,
    )

    k = int(accumulate_steps)
    if k < 1:
        raise ValueError(f"accumulate_steps must be >= 1, got {k}")
    ce_terms = make_ce_terms(model_cfg, remat, int(loss_chunk))

    def step_fn(state: "TrainState", base_params, batch):
        # Closures capture base_params per trace (construction is free at
        # trace time — no mutable state shared across traces).
        def lora_ce_terms(lora, mb):
            with jax.named_scope("lora"):
                merged = apply_lora(base_params, lora, lora_cfg)
            loss, total, aux = ce_terms(merged, mb)
            if model_cfg.moe_num_experts and k == 1:
                # Same objective as full fine-tuning: keep routing balanced
                # while adapting (train/step.py does the same). The k>1
                # path adds the aux term inside accumulated_value_and_grad.
                loss = loss + model_cfg.moe_aux_coef * aux
            return loss, total, aux

        if k > 1:
            (loss, total), grads = accumulated_value_and_grad(
                model_cfg, lora_ce_terms, k)(state.params, batch)
        else:
            def loss_fn(lora):
                loss, total, _ = lora_ce_terms(lora, batch)
                return loss, total

            (loss, total), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state.opt_state,
                                                state.params)
            new_lora = optax.apply_updates(state.params, updates)
            grad_norm = optax.global_norm(grads)
            # Non-finite guard, same contract as make_train_step: a bad
            # batch skips the update (LoRA params + opt state bitwise
            # unchanged) and flags the step for the trainer's
            # consecutive-bad-step abort.
            ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm)
            new_lora, new_opt = jax.tree.map(
                lambda new, old: jnp.where(ok, new, old),
                (new_lora, new_opt), (state.params, state.opt_state))
        metrics = {"loss": loss, "grad_norm": grad_norm,
                   "weight_tokens": total,
                   "nonfinite": (~ok).astype(jnp.int32)}
        return TrainState(step=state.step + 1, params=new_lora,
                          opt_state=new_opt), metrics

    replicated = NamedSharding(mesh, P())
    return jax.jit(
        step_fn,
        in_shardings=(state_shardings, base_shardings, None),
        out_shardings=(state_shardings, replicated),
        donate_argnums=(0,),
    )
