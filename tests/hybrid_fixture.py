"""A tiny seeded hybrid (linear-attention layers beside full ones) and the
benchmark's plain reference for it, shared by the hybrid tests."""

import importlib.util
import os

import jax

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import init_params
from runbooks_tpu.train.step import layout_invariant_init

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Two periods of (linear x3, full x1) at toy widths; key and value head
# sizes differ, as at the published size. Keys of 32, not fewer: unit keys
# in very few dimensions overlap so much that the delta rule's erasures
# nearly cancel its writes, and the model then amplifies round-off (at
# d_k 8 a bfloat16 forward is 20 % off the float32 reference, here 6 %;
# at the published widths a perturbation grows additively with depth).
TINY = dict(num_layers=8, vocab_size=512, hidden_size=128,
            intermediate_size=256, num_heads=4, num_kv_heads=4, head_dim=32,
            linear_num_heads=4, linear_key_head_dim=32,
            linear_value_head_dim=64, max_seq_len=256,
            attention_impl="xla")
AS_RUN = {
    "vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 8, "num_attention_heads": 4, "head_dim": 32,
    "rms_norm_eps": 1e-6, "linear_num_key_heads": 4,
    "linear_num_value_heads": 4, "linear_key_head_dim": 32,
    "linear_value_head_dim": 64, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True,
    "layer_period": ["linear_attention"] * 3 + ["full_attention"],
}


def load_reference():
    path = os.path.join(ROOT, "benchmark", "reference", "olmo_hybrid.py")
    spec = importlib.util.spec_from_file_location("ref_olmo_hybrid", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(**overrides):
    return get_config("olmo-hybrid-7b", **{**TINY, **overrides})


def seeded_params(cfg, seed: int):
    """As serve/api.load_model makes them from a seed."""
    with layout_invariant_init():
        return jax.jit(lambda k: init_params(cfg, k))(jax.random.key(seed))
