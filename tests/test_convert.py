"""HF weight-conversion parity: build tiny HF models (random init, no
downloads), convert their state dicts, and compare logits between the HF
torch implementation and our JAX forward. This pins the architecture
semantics (RoPE convention, fused-QKV unfusing, OPT position offset,
parallel-block wiring) against the de-facto reference implementations."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from runbooks_tpu.models.config import ModelConfig
from runbooks_tpu.models.convert import convert
from runbooks_tpu.models.transformer import forward


def compare(cfg, hf_model, tokens, atol=2e-3):
    hf_model.eval()
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(tokens))).logits.numpy()
    sd = {k: v.float().numpy() for k, v in hf_model.state_dict().items()}
    params = convert(cfg, sd)
    params = jax.tree.map(jnp.asarray, params)
    ours, _ = forward(cfg, params, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(ours), hf_logits, atol=atol,
                               rtol=2e-3)


def test_llama_parity():
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
        attention_bias=False, tie_word_embeddings=False)
    torch.manual_seed(0)
    hf = LlamaForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="llama-test", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64, dtype="float32")
    tokens = np.random.default_rng(0).integers(0, 128, (2, 12))
    compare(cfg, hf, tokens)


@pytest.mark.parametrize("mqa", [True, False])
def test_falcon_parity(mqa):
    from transformers import FalconConfig, FalconForCausalLM

    hf_cfg = FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_kv_heads=None if mqa else 2,
        multi_query=mqa, new_decoder_architecture=not mqa,
        parallel_attn=True, bias=False, alibi=False)
    torch.manual_seed(0)
    hf = FalconForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="falcon-test", vocab_size=128, hidden_size=64,
        intermediate_size=256, num_layers=2, num_heads=4,
        num_kv_heads=1 if mqa else 2, head_dim=16, max_seq_len=64,
        norm_type="layernorm", gated_mlp=False, activation="gelu",
        position_type="rope", parallel_block=True,
        shared_layer_norm=mqa, tie_embeddings=True, dtype="float32")
    tokens = np.random.default_rng(1).integers(0, 128, (2, 10))
    compare(cfg, hf, tokens)


def test_opt_parity():
    from transformers import OPTConfig, OPTForCausalLM

    hf_cfg = OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        do_layer_norm_before=True, word_embed_proj_dim=64,
        tie_word_embeddings=True)
    torch.manual_seed(0)
    hf = OPTForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="opt-test", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=4,
        head_dim=16, max_seq_len=64, norm_type="layernorm", gated_mlp=False,
        activation="relu", position_type="learned", attn_bias=True,
        mlp_bias=True, tie_embeddings=True, dtype="float32")
    tokens = np.random.default_rng(2).integers(0, 128, (2, 11))
    compare(cfg, hf, tokens)


def test_mixtral_parity():
    """HF Mixtral (llama attention + sparse MoE FFN) vs our MoE path. High
    capacity factor => no token drops, so the top-2 routed output is exact
    (HF routes densely per token with no capacity)."""
    from transformers import MixtralConfig, MixtralForCausalLM

    hf_cfg = MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
        num_local_experts=4, num_experts_per_tok=2,
        tie_word_embeddings=False)
    torch.manual_seed(0)
    hf = MixtralForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="mixtral-test", vocab_size=128, hidden_size=64,
        intermediate_size=96, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64, dtype="float32",
        moe_num_experts=4, moe_top_k=2)
    tokens = np.random.default_rng(2).integers(0, 128, (2, 12))
    compare(cfg, hf, tokens)


def test_gemma_parity():
    """Gemma: llama keys + (1+w) RMSNorm + GeGLU + scaled tied embeddings."""
    from transformers import GemmaConfig, GemmaForCausalLM

    hf_cfg = GemmaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=1,
        head_dim=16, max_position_embeddings=64, rms_norm_eps=1e-6,
        hidden_act="gelu_pytorch_tanh", tie_word_embeddings=True)
    torch.manual_seed(0)
    hf = GemmaForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="gemma-test", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=1,
        head_dim=16, max_seq_len=64, norm_eps=1e-6, activation="gelu",
        tie_embeddings=True, embed_scale=True, dtype="float32")
    tokens = np.random.default_rng(3).integers(0, 128, (2, 12))
    compare(cfg, hf, tokens)


def test_gpt2_parity():
    """GPT-2: Conv1D (no transpose), fused qkv, learned positions."""
    from transformers import GPT2Config, GPT2LMHeadModel

    hf_cfg = GPT2Config(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64,
        activation_function="gelu_new")
    torch.manual_seed(0)
    hf = GPT2LMHeadModel(hf_cfg)
    cfg = ModelConfig(
        name="gpt2-test", vocab_size=128, hidden_size=64,
        intermediate_size=256, num_layers=2, num_heads=4, num_kv_heads=4,
        head_dim=16, max_seq_len=64, norm_type="layernorm", gated_mlp=False,
        activation="gelu", position_type="learned", attn_bias=True,
        mlp_bias=True, tie_embeddings=True, dtype="float32")
    tokens = np.random.default_rng(4).integers(0, 128, (2, 10))
    compare(cfg, hf, tokens)
