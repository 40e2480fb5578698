"""HuggingFace checkpoint conversion: state dict -> runbooks-tpu param tree.

The reference delegates model import to an external image
(substratusai/model-loader-huggingface — reference: examples/
facebook-opt-125m/base-model.yaml); here conversion is in-framework so the
loader workload (models/loader.py) can import Llama/Falcon/OPT checkpoints
into the stacked-layer layout natively.

Conventions verified against HF implementations by the parity tests
(tests/test_convert.py builds tiny HF models and compares logits):
- Llama: HF rotate_half == our split-half RoPE, weights transpose directly.
- Falcon: fused query_key_value is unfused; 7b-style MQA (1 kv head) and
  40b-style grouped-KV both supported; parallel block with shared or split
  layernorms.
- OPT: learned positions with HF's +2 row offset dropped; pre-LN variant.
"""

from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

from runbooks_tpu.models.config import ModelConfig

Array = np.ndarray
StateDict = Mapping[str, Array]


def _t(x: Array) -> Array:
    return np.ascontiguousarray(np.asarray(x).T)


def _stack(arrs) -> Array:
    return np.stack([np.asarray(a) for a in arrs])


def convert_llama(cfg: ModelConfig, sd: StateDict) -> Dict:
    L = cfg.num_layers
    p = lambda i, name: np.asarray(sd[f"model.layers.{i}.{name}"])
    params = {
        "embed": np.asarray(sd["model.embed_tokens.weight"]),
        "final_norm": {"scale": np.asarray(sd["model.norm.weight"])},
        "layers": {
            "attn": {
                "wq": _stack(_t(p(i, "self_attn.q_proj.weight"))
                             for i in range(L)),
                "wk": _stack(_t(p(i, "self_attn.k_proj.weight"))
                             for i in range(L)),
                "wv": _stack(_t(p(i, "self_attn.v_proj.weight"))
                             for i in range(L)),
                "wo": _stack(_t(p(i, "self_attn.o_proj.weight"))
                             for i in range(L)),
            },
            "mlp": {
                "wi_gate": _stack(_t(p(i, "mlp.gate_proj.weight"))
                                  for i in range(L)),
                "wi_up": _stack(_t(p(i, "mlp.up_proj.weight"))
                                for i in range(L)),
                "wo": _stack(_t(p(i, "mlp.down_proj.weight"))
                             for i in range(L)),
            },
            "ln1": {"scale": _stack(p(i, "input_layernorm.weight")
                                    for i in range(L))},
            "ln2": {"scale": _stack(p(i, "post_attention_layernorm.weight")
                                    for i in range(L))},
        },
    }
    if not cfg.tie_embeddings:
        head = sd.get("lm_head.weight")
        params["head"] = (_t(head) if head is not None
                          else _t(params["embed"]))
    return params


def convert_falcon(cfg: ModelConfig, sd: StateDict) -> Dict:
    L, h = cfg.num_layers, cfg.hidden_size
    nq, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rep = nq // nkv

    def unfuse(i):
        w = np.asarray(sd[f"transformer.h.{i}.self_attention"
                          f".query_key_value.weight"])   # [(nkv*(rep+2))*d, h]
        w = w.reshape(nkv, rep + 2, d, h)
        q = w[:, :rep].reshape(nq * d, h)
        k = w[:, rep].reshape(nkv * d, h)
        v = w[:, rep + 1].reshape(nkv * d, h)
        return _t(q), _t(k), _t(v)

    qs, ks, vs = zip(*(unfuse(i) for i in range(L)))
    g = lambda i, name: np.asarray(sd[f"transformer.h.{i}.{name}"])
    layers: Dict = {
        "attn": {
            "wq": _stack(qs), "wk": _stack(ks), "wv": _stack(vs),
            "wo": _stack(_t(g(i, "self_attention.dense.weight"))
                         for i in range(L)),
        },
        "mlp": {
            "wi": _stack(_t(g(i, "mlp.dense_h_to_4h.weight"))
                         for i in range(L)),
            "wo": _stack(_t(g(i, "mlp.dense_4h_to_h.weight"))
                         for i in range(L)),
        },
    }
    if cfg.shared_layer_norm:
        layers["ln1"] = {
            "scale": _stack(g(i, "input_layernorm.weight")
                            for i in range(L)),
            "bias": _stack(g(i, "input_layernorm.bias") for i in range(L)),
        }
    else:
        layers["ln1"] = {
            "scale": _stack(g(i, "ln_attn.weight") for i in range(L)),
            "bias": _stack(g(i, "ln_attn.bias") for i in range(L)),
        }
        layers["ln2"] = {
            "scale": _stack(g(i, "ln_mlp.weight") for i in range(L)),
            "bias": _stack(g(i, "ln_mlp.bias") for i in range(L)),
        }
    return {
        "embed": np.asarray(sd["transformer.word_embeddings.weight"]),
        "final_norm": {
            "scale": np.asarray(sd["transformer.ln_f.weight"]),
            "bias": np.asarray(sd["transformer.ln_f.bias"]),
        },
        "layers": layers,
    }


def convert_opt(cfg: ModelConfig, sd: StateDict) -> Dict:
    L = cfg.num_layers
    g = lambda i, name: np.asarray(sd[f"model.decoder.layers.{i}.{name}"])
    params = {
        "embed": np.asarray(sd["model.decoder.embed_tokens.weight"]),
        # HF OPT offsets learned positions by 2 rows.
        "pos_embed": np.asarray(
            sd["model.decoder.embed_positions.weight"])[2:],
        "final_norm": {
            "scale": np.asarray(sd["model.decoder.final_layer_norm.weight"]),
            "bias": np.asarray(sd["model.decoder.final_layer_norm.bias"]),
        },
        "layers": {
            "attn": {
                "wq": _stack(_t(g(i, "self_attn.q_proj.weight"))
                             for i in range(L)),
                "wk": _stack(_t(g(i, "self_attn.k_proj.weight"))
                             for i in range(L)),
                "wv": _stack(_t(g(i, "self_attn.v_proj.weight"))
                             for i in range(L)),
                "wo": _stack(_t(g(i, "self_attn.out_proj.weight"))
                             for i in range(L)),
                "bq": _stack(g(i, "self_attn.q_proj.bias")
                             for i in range(L)),
                "bk": _stack(g(i, "self_attn.k_proj.bias")
                             for i in range(L)),
                "bv": _stack(g(i, "self_attn.v_proj.bias")
                             for i in range(L)),
                "bo": _stack(g(i, "self_attn.out_proj.bias")
                             for i in range(L)),
            },
            "mlp": {
                "wi": _stack(_t(g(i, "fc1.weight")) for i in range(L)),
                "bi": _stack(g(i, "fc1.bias") for i in range(L)),
                "wo": _stack(_t(g(i, "fc2.weight")) for i in range(L)),
                "bo": _stack(g(i, "fc2.bias") for i in range(L)),
            },
            "ln1": {
                "scale": _stack(g(i, "self_attn_layer_norm.weight")
                                for i in range(L)),
                "bias": _stack(g(i, "self_attn_layer_norm.bias")
                               for i in range(L)),
            },
            "ln2": {
                "scale": _stack(g(i, "final_layer_norm.weight")
                                for i in range(L)),
                "bias": _stack(g(i, "final_layer_norm.bias")
                               for i in range(L)),
            },
        },
    }
    return params


def convert_mixtral(cfg: ModelConfig, sd: StateDict) -> Dict:
    """Mixtral = llama attention + per-layer MoE FFN. HF layout:
    block_sparse_moe.gate.weight [E, h] (router) and
    block_sparse_moe.experts.{e}.w1/w3/w2 (gate/up/down)."""
    L, E = cfg.num_layers, cfg.moe_num_experts
    p = lambda i, name: np.asarray(sd[f"model.layers.{i}.{name}"])

    def expert(i, e, w):
        return _t(p(i, f"block_sparse_moe.experts.{e}.{w}.weight"))

    params = {
        "embed": np.asarray(sd["model.embed_tokens.weight"]),
        "final_norm": {"scale": np.asarray(sd["model.norm.weight"])},
        "layers": {
            "attn": {
                "wq": _stack(_t(p(i, "self_attn.q_proj.weight"))
                             for i in range(L)),
                "wk": _stack(_t(p(i, "self_attn.k_proj.weight"))
                             for i in range(L)),
                "wv": _stack(_t(p(i, "self_attn.v_proj.weight"))
                             for i in range(L)),
                "wo": _stack(_t(p(i, "self_attn.o_proj.weight"))
                             for i in range(L)),
            },
            "moe": {
                "router": _stack(_t(p(i, "block_sparse_moe.gate.weight"))
                                 for i in range(L)),      # [L, h, E]
                "wi_gate": _stack(
                    _stack(expert(i, e, "w1") for e in range(E))
                    for i in range(L)),                   # [L, E, h, m]
                "wi_up": _stack(
                    _stack(expert(i, e, "w3") for e in range(E))
                    for i in range(L)),
                "wo": _stack(
                    _stack(expert(i, e, "w2") for e in range(E))
                    for i in range(L)),                   # [L, E, m, h]
            },
            "ln1": {"scale": _stack(p(i, "input_layernorm.weight")
                                    for i in range(L))},
            "ln2": {"scale": _stack(p(i, "post_attention_layernorm.weight")
                                    for i in range(L))},
        },
    }
    if not cfg.tie_embeddings:
        head = sd.get("lm_head.weight")
        params["head"] = (_t(head) if head is not None
                          else _t(params["embed"]))
    return params


def convert_gemma(cfg: ModelConfig, sd: StateDict) -> Dict:
    """Gemma uses llama key names but RMSNorm computes x * (1 + w): fold
    the +1 into the stored scales. Head is tied to the embedding."""
    params = convert_llama(cfg, sd)
    params["final_norm"]["scale"] = params["final_norm"]["scale"] + 1.0
    for ln in ("ln1", "ln2"):
        params["layers"][ln]["scale"] = params["layers"][ln]["scale"] + 1.0
    return params


def convert_gpt2(cfg: ModelConfig, sd: StateDict) -> Dict:
    """GPT-2: Conv1D weights are already [in, out] (no transpose), the
    attention projection is a fused c_attn [h, 3h] split into q/k/v, and
    learned positions have no row offset (unlike OPT's +2)."""
    L, h = cfg.num_layers, cfg.hidden_size
    g = lambda i, name: np.asarray(sd[f"transformer.h.{i}.{name}"])

    def split_qkv(i):
        w = g(i, "attn.c_attn.weight")        # [h, 3h]
        b = g(i, "attn.c_attn.bias")          # [3h]
        return (w[:, :h], w[:, h:2 * h], w[:, 2 * h:],
                b[:h], b[h:2 * h], b[2 * h:])

    qs, ks, vs, bqs, bks, bvs = zip(*(split_qkv(i) for i in range(L)))
    return {
        "embed": np.asarray(sd["transformer.wte.weight"]),
        "pos_embed": np.asarray(sd["transformer.wpe.weight"]),
        "final_norm": {
            "scale": np.asarray(sd["transformer.ln_f.weight"]),
            "bias": np.asarray(sd["transformer.ln_f.bias"]),
        },
        "layers": {
            "attn": {
                "wq": _stack(qs), "wk": _stack(ks), "wv": _stack(vs),
                "bq": _stack(bqs), "bk": _stack(bks), "bv": _stack(bvs),
                "wo": _stack(g(i, "attn.c_proj.weight") for i in range(L)),
                "bo": _stack(g(i, "attn.c_proj.bias") for i in range(L)),
            },
            "mlp": {
                "wi": _stack(g(i, "mlp.c_fc.weight") for i in range(L)),
                "bi": _stack(g(i, "mlp.c_fc.bias") for i in range(L)),
                "wo": _stack(g(i, "mlp.c_proj.weight") for i in range(L)),
                "bo": _stack(g(i, "mlp.c_proj.bias") for i in range(L)),
            },
            "ln1": {
                "scale": _stack(g(i, "ln_1.weight") for i in range(L)),
                "bias": _stack(g(i, "ln_1.bias") for i in range(L)),
            },
            "ln2": {
                "scale": _stack(g(i, "ln_2.weight") for i in range(L)),
                "bias": _stack(g(i, "ln_2.bias") for i in range(L)),
            },
        },
    }


CONVERTERS = {
    "mixtral": convert_mixtral,  # before "llama": shares its attention
    "gemma": convert_gemma,      # likewise llama-keyed
    "gpt2": convert_gpt2,
    "llama": convert_llama,
    "falcon": convert_falcon,
    "opt": convert_opt,
}


def family_of(cfg: ModelConfig) -> str:
    name = cfg.name.lower()
    for fam in CONVERTERS:
        if fam in name:
            return fam
    # Structural fallback
    if cfg.moe_num_experts:
        return "mixtral"
    if cfg.parallel_block:
        return "falcon"
    if cfg.position_type == "learned":
        return "opt"
    return "llama"


def convert(cfg: ModelConfig, state_dict: StateDict,
            dtype: str = "float32", quantize: str = "none") -> Dict:
    """HF state dict -> param tree (numpy, cast to `dtype`).

    quantize="int8"|"int4" applies blockwise weight-only quantization to
    the attention/MLP matmuls right after conversion (ops/quantization.py),
    walking stacked weights one layer at a time so importing a 70B-class
    checkpoint peaks at ~one f32 layer above the packed size."""
    import jax

    if cfg.has_window:
        raise NotImplementedError(
            "no converter lays a checkpoint out as params['window_layers'] "
            "(a stack a position of the period, KV heads and a sink of "
            "their own): models with sliding_attention layers run from "
            "seeded weights only (docs/window-full-models.md)")
    params = CONVERTERS[family_of(cfg)](cfg, state_dict)
    params = jax.tree.map(lambda x: np.asarray(x, dtype=dtype), params)
    if quantize != "none":
        from runbooks_tpu.ops.quantization import quantize_params

        params = quantize_params(params, quantize)
    return params


def load_torch_state_dict(model_dir: str) -> Dict[str, Array]:
    """Read a local HF checkpoint directory (safetensors preferred, torch
    .bin fallback) into a numpy state dict."""
    import glob
    import os

    sd: Dict[str, Array] = {}
    st_files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if st_files:
        from safetensors import safe_open

        for path in st_files:
            with safe_open(path, framework="np") as f:
                for key in f.keys():
                    sd[key] = f.get_tensor(key)
        return sd
    import torch

    for path in sorted(glob.glob(os.path.join(model_dir, "*.bin"))):
        part = torch.load(path, map_location="cpu", weights_only=True)
        for key, val in part.items():
            sd[key] = val.float().numpy()
    if not sd:
        raise FileNotFoundError(f"no safetensors/bin weights in {model_dir}")
    return sd
