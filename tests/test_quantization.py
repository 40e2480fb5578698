"""Quantized serving fast path: blockwise int8/int4 weight-only
quantization, the fused dequant-matmul, int8 KV cache, and the flash
cached-prefill route.

Oracles:
- pack/unpack is bit-exact; int8 round-trips exactly on power-of-two-scale
  grids; int4 error is bounded by half a quantization step per block.
- quantized_matmul == x @ dequantize(w) (scales-post-dot is algebraically
  exact, so only accumulation-order noise remains).
- a tiny quantized model's logits track the full-precision model and greedy
  decode agrees through the engine (weights AND int8 KV).
- the engine's prefill routes through the Pallas flash kernel when the
  query bucket is >= the flash min tile (kernel-count check like
  tests/test_flash_attention.py's) and matches the XLA path numerically.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import KVCache, forward, init_params
from runbooks_tpu.ops.quantization import (
    QuantizedArray,
    dequantize,
    pack_for_checkpoint,
    pack_int4,
    quantize,
    quantize_params,
    quantized_matmul,
    tree_weight_bytes,
    unpack_from_checkpoint,
    unpack_int4,
)
from runbooks_tpu.serve.engine import InferenceEngine, Request
from tests.cache_paths import MODE_VIEW_IDS, MODES_AND_VIEWS, worst_gap


def tiny_cfg(**over):
    return dataclasses.replace(
        get_config("llama2-7b"), vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64, dtype="float32", **over)


# ---------------------------------------------------------------------------
# Pack / round-trip exactness
# ---------------------------------------------------------------------------

def test_int4_pack_unpack_exact():
    rng = np.random.default_rng(0)
    q = rng.integers(-7, 8, (6, 32, 10)).astype(np.int8)
    out = np.asarray(unpack_int4(pack_int4(jnp.asarray(q))))
    np.testing.assert_array_equal(out, q)


def test_int8_roundtrip_exact_on_grid():
    """Weights lying exactly on a power-of-two-scale int8 grid survive
    quantize->dequantize bit-exactly (127*2^e, /127, and q*2^e are all
    exact in f32)."""
    rng = np.random.default_rng(1)
    nb, bs, out = 3, 16, 8
    q = rng.integers(-127, 128, (nb, bs, out)).astype(np.float32)
    q[:, 0, :] = 127.0  # pin per-block amax so the scale is exactly 2^e
    scales = 2.0 ** rng.integers(-8, 2, (nb, 1, out)).astype(np.float32)
    w = (q * scales).reshape(nb * bs, out)
    qa = quantize(w, bits=8, block_size=bs)
    np.testing.assert_array_equal(np.asarray(dequantize(qa)), w)


def test_int4_error_bounded_by_half_step():
    rng = np.random.default_rng(2)
    w = rng.standard_normal((64, 16)).astype(np.float32)
    qa = quantize(w, bits=4, block_size=16)
    err = np.abs(np.asarray(dequantize(qa)) - w)
    # One quantization step per (block, channel) is amax/7; rounding keeps
    # each element within half a step (+ f32 noise).
    amax = np.abs(w.reshape(4, 16, 16)).max(axis=1, keepdims=True)
    step = np.broadcast_to(amax / 7.0, (4, 16, 16)).reshape(64, 16)
    assert (err <= step / 2 + 1e-6).all()


@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_matmul_matches_dequant(bits):
    rng = np.random.default_rng(3)
    w = rng.standard_normal((64, 24)).astype(np.float32)
    x = rng.standard_normal((2, 5, 64)).astype(np.float32)
    qa = quantize(w, bits=bits, block_size=16)
    ref = np.asarray(x @ np.asarray(dequantize(qa)))
    got = np.asarray(quantized_matmul(jnp.asarray(x), qa, jnp.float32))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_quantize_params_structure_and_checkpoint_roundtrip():
    cfg = tiny_cfg()
    params = quantize_params(
        jax.tree.map(lambda x: x, init_params(cfg, jax.random.key(0))),
        "int4", block_size=32)
    attn = params["layers"]["attn"]
    mlp = params["layers"]["mlp"]
    for key in ("wq", "wk", "wv", "wo"):
        assert isinstance(attn[key], QuantizedArray), key
    for key in ("wi_gate", "wi_up", "wo"):
        assert isinstance(mlp[key], QuantizedArray), key
    # Norms/embeddings stay full precision.
    assert not isinstance(params["embed"], QuantizedArray)
    assert not isinstance(params["layers"]["ln1"]["scale"], QuantizedArray)
    # int4 shrinks total weight bytes well below half of f32.
    f32_bytes = tree_weight_bytes(init_params(cfg, jax.random.key(0)))
    assert tree_weight_bytes(params) < f32_bytes / 2
    # Checkpoint pack (plain dicts) -> unpack reconstructs QuantizedArrays
    # with identical contents and metadata.
    restored = unpack_from_checkpoint(pack_for_checkpoint(params))
    r = restored["layers"]["attn"]["wq"]
    assert isinstance(r, QuantizedArray)
    assert (r.bits, r.block_size) == (attn["wq"].bits, attn["wq"].block_size)
    np.testing.assert_array_equal(np.asarray(r.values),
                                  np.asarray(attn["wq"].values))


# ---------------------------------------------------------------------------
# Model-level parity
# ---------------------------------------------------------------------------

def test_quantized_logits_parity_tiny_model():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    q8 = quantize_params(jax.tree.map(lambda x: x, params), "int8",
                         block_size=32)
    toks = jnp.asarray([[5, 9, 17, 3, 2, 44, 7, 101]], jnp.int32)
    ref, _ = forward(cfg, params, toks)
    got, _ = forward(cfg, q8, toks)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(ref - got))) < 0.05 * max(scale, 1.0)
    assert (jnp.argmax(ref[:, -1], -1) == jnp.argmax(got[:, -1], -1)).all()


def test_quantized_engine_greedy_matches_bf16_weights():
    """int8-weight + int8-KV engine greedy decode agrees with the
    full-precision engine on short prompts (the acceptance parity check —
    short rollouts; tiny random models have near-tied logits further out)."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    q8 = quantize_params(jax.tree.map(lambda x: x, params), "int8",
                         block_size=32)
    prompts = [[5, 9, 17], [3, 4, 5, 6, 7, 8, 9, 10]]

    def run(p, quantize_kv):
        eng = InferenceEngine(cfg, p, max_slots=2, quantize_kv=quantize_kv)
        reqs = [Request(prompt_tokens=pr, max_tokens=4, temperature=0.0)
                for pr in prompts]
        eng.generate(reqs)
        return [r.output_tokens for r in reqs]

    assert run(params, False) == run(q8, True)


def test_int8_kv_decode_greedy_agreement():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    f32 = InferenceEngine(cfg, params, max_slots=2, quantize_kv=False)
    i8 = InferenceEngine(cfg, params, max_slots=2, quantize_kv=True)
    assert i8.cache.quantized and i8.cache.k.dtype == jnp.int8
    assert not f32.cache.quantized
    for prompt in ([5, 9, 17], [42]):
        a = Request(prompt_tokens=list(prompt), max_tokens=4,
                    temperature=0.0)
        b = Request(prompt_tokens=list(prompt), max_tokens=4,
                    temperature=0.0)
        f32.generate([a])
        i8.generate([b])
        assert a.output_tokens == b.output_tokens, prompt


@pytest.mark.parametrize("mode,view", MODES_AND_VIEWS, ids=MODE_VIEW_IDS)
@pytest.mark.parametrize("kv_heads", [1, 2], ids=["mqa", "gqa"])
def test_int8_pool_write_modes_and_views_track_full_forward(kv_heads, mode,
                                                            view):
    """Values and scales land at the same [layer, row, slot] in both write
    modes, and a short view reads both: through the int8 pool the logits
    stay within int8's error of the forward without a cache (0.045 /
    0.033 here, logits of order 1)."""
    cfg = dataclasses.replace(tiny_cfg(), num_kv_heads=kv_heads)
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0,
                                cfg.vocab_size)
    exact = worst_gap(cfg, params, tokens, mode, view)
    int8 = worst_gap(cfg, params, tokens, mode, view, int8_pool=True)
    assert exact < 2e-5 < int8 < 0.1


def test_int8_kv_halves_cache_bytes():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    f32 = InferenceEngine(cfg, params, max_slots=2)
    i8 = InferenceEngine(cfg, params, max_slots=2, quantize_kv=True)
    full = f32.cache.k.nbytes + f32.cache.v.nbytes
    packed = (i8.cache.k.nbytes + i8.cache.v.nbytes
              + i8.cache.k_scale.nbytes + i8.cache.v_scale.nbytes)
    # int8 + one f32 scale per head_dim=16 row: 16 bytes -> 4+... well under
    # 60% of the f32 cache; at bf16/head_dim=128 serving shapes it is ~51%.
    assert packed < 0.6 * full


# ---------------------------------------------------------------------------
# Flash cached-prefill
# ---------------------------------------------------------------------------

def _count_pallas_calls(jaxpr, n=0):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                n = _count_pallas_calls(v.jaxpr, n)
            elif hasattr(v, "eqns"):
                n = _count_pallas_calls(v, n)
    return n


def test_flash_cached_prefill_matches_xla_and_uses_kernel():
    cfg_x = tiny_cfg()
    cfg_f = tiny_cfg(attention_impl="flash", flash_block_q=16,
                     flash_block_k=16)
    params = init_params(cfg_x, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 32), 1, 128)

    # Scalar-index chunked prefill.
    ref, ref_cache = forward(cfg_x, params, toks,
                             cache=KVCache.create(cfg_x, 2, 64))
    got, got_cache = forward(cfg_f, params, toks,
                             cache=KVCache.create(cfg_f, 2, 64))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    # Position-scatter mode under a bucketed view (the engine's layout).
    pos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32)[None], (2, 32))
    ref2, _ = forward(cfg_x, params, toks, positions=pos,
                      cache=KVCache.create(cfg_x, 2, 65), cache_view=48)
    got2, _ = forward(cfg_f, params, toks, positions=pos,
                      cache=KVCache.create(cfg_f, 2, 65), cache_view=48)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(ref2),
                               rtol=2e-4, atol=2e-4)

    # The kernel is actually on the cached-prefill path; decode (s=1)
    # stays XLA.
    def prefill(p, t):
        return forward(cfg_f, p, t, cache=KVCache.create(cfg_f, 2, 64))[0]

    def decode(p, t):
        return forward(cfg_f, p, t, cache=KVCache.create(cfg_f, 2, 64))[0]

    assert _count_pallas_calls(
        jax.make_jaxpr(prefill)(params, toks).jaxpr) >= 1
    assert _count_pallas_calls(
        jax.make_jaxpr(decode)(params, toks[:, :1]).jaxpr) == 0


def test_engine_prefill_routes_through_flash_kernel():
    """The ENGINE's jitted prefill exercises the flash kernel for
    long-bucket prefills (the VERDICT Missing-4 acceptance check): trace
    the exact function the engine dispatches and count pallas calls."""
    cfg = tiny_cfg(attention_impl="flash", flash_block_q=16,
                   flash_block_k=16)
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=2)

    rows, bucket = 1, 32
    tokens = jnp.zeros((rows, bucket), jnp.int32)
    positions = jnp.broadcast_to(
        jnp.arange(bucket, dtype=jnp.int32)[None], (rows, bucket))
    args = (engine.params, engine.cache, tokens, positions,
            jnp.zeros(rows, jnp.int32), jnp.full(rows, bucket - 1,
                                                 jnp.int32),
            jax.random.key(0), jnp.zeros(rows, jnp.float32),
            jnp.zeros(rows, jnp.int32), jnp.ones(rows, jnp.float32))
    jaxpr = jax.make_jaxpr(engine._prefill)(*args)
    assert _count_pallas_calls(jaxpr.jaxpr) >= 1

    # And end-to-end: the flash-prefill engine produces the same greedy
    # tokens as the XLA engine.
    plain = InferenceEngine(tiny_cfg(), params, max_slots=2)
    for eng in (engine, plain):
        eng.reset()
    prompt = list(range(1, 21))  # 20 tokens -> 32-bucket >= flash min tile
    outs = []
    for eng in (engine, plain):
        r = Request(prompt_tokens=list(prompt), max_tokens=6,
                    temperature=0.0)
        eng.generate([r])
        outs.append(r.output_tokens)
    assert outs[0] == outs[1]
