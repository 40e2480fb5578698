"""The cache and the engine under `debug-lfm2` (tests/test_lfm2.py has the
model against its reference and says what the tolerances mean): prefill
then decode through the K/V leaves (the 2 full layers) and the conv leaf
(the 7 conv layers, the leading one first, no state beside it), rows of
unequal length in one bucket, a parked row, a slot taken by a second
request, the serving engine's census, counters and refusals. A file of its
own so that the suite's workers share the load."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.transformer import KVCache, forward, init_params
from tests.test_lfm2 import (
    CONV,
    TOL,
    reference_logits,
    seeded,
    tokens_for,
    toy,
)


@pytest.fixture(autouse=True)
def exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def test_prefill_then_decode_through_the_tails_matches_reference():
    """Two rows of different lengths prefilled in one padded call
    (position-scatter mode, padding parked and masked), then decoded a
    token at a time: every logit equals the reference's full forward
    without a cache. Halfway row 1 is parked for three steps (not alive:
    its position at the trash slot, its token masked): its tails do not
    move, and it goes on exactly where it stopped."""
    cfg = toy()
    p = seeded(cfg, 7)
    seqs = [tokens_for(cfg, 40, 1), tokens_for(cfg, 33, 2)]
    n_pre = [22, 11]
    max_len, bucket, view = 48, 32, 48
    cache = KVCache.create(cfg, 2, max_len, trash_slot=True)
    assert cache.conv.shape == (7, 2, 2, cfg.hidden_size) \
        and cache.state is None and cache.k.shape[0] == 2
    toks = np.zeros((2, bucket), np.int32)
    pos = np.full((2, bucket), max_len, np.int32)
    for r, (s, n) in enumerate(zip(seqs, n_pre)):
        toks[r, :n], pos[r, :n] = s[:n], np.arange(n)
    logits, cache = forward(cfg, p, jnp.asarray(toks),
                            positions=jnp.asarray(pos), cache=cache,
                            token_mask=jnp.asarray(pos < max_len))
    want = [reference_logits(cfg, 7, s) for s in seqs]
    for r, n in enumerate(n_pre):
        np.testing.assert_allclose(np.asarray(logits[r, :n]), want[r][:n],
                                   atol=TOL)
    step = jax.jit(lambda c, t, q, m: forward(
        cfg, p, t, positions=q, cache=c, cache_view=view, token_mask=m))
    at = np.array(n_pre, np.int32)
    for i in range(18):
        alive = np.array([True, not 5 <= i < 8])
        t = np.array([[s[a]] for s, a in zip(seqs, at)], np.int32)
        q = np.where(alive, at, max_len).astype(np.int32)
        before = np.asarray(cache.conv)
        logits, cache = step(cache, jnp.asarray(t), jnp.asarray(q[:, None]),
                             jnp.asarray(alive[:, None]))
        for r in range(2):
            if alive[r]:
                np.testing.assert_allclose(np.asarray(logits[r, 0]),
                                           want[r][at[r]], atol=TOL)
            else:
                np.testing.assert_array_equal(
                    np.asarray(cache.conv)[:, r], before[:, r])
        at = at + alive


def test_padding_leaves_a_zero_tail_and_the_tail_is_the_last_two_tokens():
    """Of a bucket's row the conv leaf keeps z at the row's last 2 VALID
    tokens, whatever lies behind them; a whole padding row keeps zeros;
    the flash cached prefill writes the same leaves as the XLA one."""
    import dataclasses

    base = toy(flash_block_q=16, flash_block_k=16)
    p = init_params(base, jax.random.key(7))
    s = tokens_for(base, 27, 3)
    toks, pos = np.zeros((2, 32), np.int32), np.full((2, 32), 40, np.int32)
    toks[0, :27], pos[0, :27] = s, np.arange(27)
    out, tails = {}, {}
    for impl in ("xla", "flash"):
        cfg = dataclasses.replace(base, attention_impl=impl)
        cache = KVCache.create(cfg, 2, 40, trash_slot=True)
        out[impl], cache = forward(
            cfg, p, jnp.asarray(toks), positions=jnp.asarray(pos),
            cache=cache, token_mask=jnp.asarray(pos < 40))
        tails[impl] = np.asarray(cache.conv)
    np.testing.assert_allclose(out["flash"][0, :27], out["xla"][0, :27],
                               atol=TOL)
    np.testing.assert_allclose(tails["flash"], tails["xla"], atol=TOL)
    assert not tails["xla"][:, 1].any() and tails["xla"][:, 0].all(-1).all()
    # The same 27 tokens in an exact-length call leave the same tail.
    cache = KVCache.create(base, 1, 40)
    _, cache = forward(base, p, jnp.asarray(s)[None], cache=cache)
    np.testing.assert_allclose(np.asarray(cache.conv)[:, 0],
                               tails["xla"][:, 0], atol=TOL)


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------

def test_engine_slots_at_different_lengths_and_a_reused_slot():
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    cfg = toy(attention_impl="flash", flash_block_q=16, flash_block_k=16)
    p = seeded(cfg, 13)
    eng = InferenceEngine(cfg, p, max_slots=2, max_seq_len=64,
                          decode_chunk=4)
    prompts = [tokens_for(cfg, n, seed).tolist()   # the third reuses a slot
               for n, seed in ((17, 6), (29, 7), (20, 8))]
    budgets = (3, 21, 5)
    reqs = [Request(prompt_tokens=list(q), max_tokens=m, temperature=0.0)
            for q, m in zip(prompts, budgets)]
    eng.generate(reqs)
    for q, r in zip(prompts, reqs):
        # The second occupant of a slot starts from a zero tail: its
        # tokens are the reference's, which knows no other request.
        seq = np.asarray(q + r.output_tokens, np.int32)
        logits = reference_logits(cfg, 13, seq)
        rows = np.arange(len(q) - 1, len(seq) - 1)
        gap = logits[rows].max(-1) - logits[rows, r.output_tokens]
        assert len(r.output_tokens) == r.max_tokens and gap.max() <= TOL
    # Every expert is held: nothing is routed elsewhere, and the held
    # experts got every real token x top-2 x 8 sparse layers.
    stats = eng.moe_stats()
    fed = sum(len(q) for q in prompts) + sum(m - 1 for m in budgets)
    assert stats["elsewhere"] == 0
    assert sum(stats["expert_tokens"]) == fed * 2 * 8
    assert len(stats["expert_tokens"]) == cfg.moe_num_experts
    occ = eng.kv_occupancy()
    # The tails alone: 7 conv layers x 2 slots x 2 tokens x hidden, f32.
    assert occ["recurrent_state_bytes"] == 7 * 2 * 2 * cfg.hidden_size * 4
    assert occ["kv_pool_bytes"] == 2 * eng.cache.k.nbytes \
        and eng.cache.k.shape[0] == cfg.layers_of("full_attention") == 2
    groups = eng.memory_groups()
    assert groups["kv_cache"].conv is None \
        and groups["recurrent_state"] == (None, eng.cache.conv)
    assert eng.cache.conv.shape[0] == cfg.layers_of(CONV) == 7


def test_warmup_compiles_the_programs_the_requests_then_use():
    """The warm-up the other models have: after it two requests of
    different buckets, a decode chunk and a slot that changes hands
    compile nothing."""
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.serve.engine import InferenceEngine, Request

    sentinel = obs_device.SENTINEL
    if not sentinel.install():
        pytest.skip("jax.monitoring unavailable; sentinel cannot verify")
    cfg = toy()
    eng = InferenceEngine(cfg, seeded(cfg, 1), max_slots=2, max_seq_len=64,
                          decode_chunk=4)
    eng.warmup()
    # Off the TPU the grouped products are ragged_dot: no tile to name.
    assert eng.warmup_census["gmm_tiling"] == {}
    try:
        total = sentinel.total
        reqs = [Request(prompt_tokens=tokens_for(cfg, n, n).tolist(),
                        max_tokens=m, temperature=0.0)
                for n, m in ((21, 6), (40, 3), (9, 5))]
        eng.generate(reqs)
        assert [len(r.output_tokens) for r in reqs] == [6, 3, 5]
        assert sentinel.total == total, "compiled under traffic"
    finally:
        eng.release_steady()


def test_census_names_the_grouped_products_tiles(monkeypatch):
    """engine.gmm_tiling (warmup_census, GET /debug/programs) at the
    published widths, as on a TPU: every prefill program (bucket x rows)
    and decode view with the tiles models/moe's chooser gives its sparse
    layers' products — a chunk of 2048 tokens whatever the burst's rows,
    one tile of slots x top_k rows in decode; nothing for a dense model."""
    import types

    import runbooks_tpu.utils.hw as hw
    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.serve.engine import InferenceEngine

    monkeypatch.setattr(hw, "on_tpu", lambda: True)
    cfg = get_config("lfm2-24b-a2b")

    def census(cfg):
        from runbooks_tpu.serve.engine import dispatch_shapes

        # A budget of two windows: every bucket has both row counts.
        return InferenceEngine.gmm_tiling.func(types.SimpleNamespace(
            cfg=cfg, max_slots=8, view_buckets=(512, 2048),
            dispatch_shapes=dispatch_shapes((16, 1024, 2048), 4096, 8)))

    chunk = {"gate_up": [256, 2048, 768], "down": [256, 1536, 1024]}
    step = {"gate_up": [32, 1024, 1024], "down": [32, 1024, 1024]}
    assert census(cfg) == {
        "prefill_b16r1": {"gate_up": [64, 1024, 1024],
                          "down": [64, 1024, 1024]},
        "prefill_b16r8": chunk,
        "prefill_b1024r1": chunk, "prefill_b1024r8": chunk,
        "prefill_b2048r1": chunk, "prefill_b2048r8": chunk,
        "decode_v512": step, "decode_v2048": step}
    assert census(get_config("falcon-7b")) == {}


@pytest.mark.parametrize("options,text", [
    (dict(speculative="ngram"), "speculative decoding"),
    (dict(adapter_pool=2), "adapter pool"),
    ("paged", "kv_paging: paged"),
    ("prefix", "prefix registration"),
    ("prefix_from_slot", "prefix registration"),
    ("warm_prefix", "prefix registration"),
    ("auto_prefix_chat", "auto_prefix_chat"),
])
def test_engine_refusals_hold_for_a_tail_too(options, text):
    from runbooks_tpu.serve.engine import InferenceEngine

    cfg = toy()
    p = seeded(cfg, 0)
    kw = dict(max_slots=2, max_seq_len=64)
    match = text if options == "auto_prefix_chat" \
        else f"{text}.*short-convolution"
    with pytest.raises(ValueError, match=match):
        if options == "paged":
            from runbooks_tpu.serve.paging import PagedInferenceEngine

            PagedInferenceEngine(cfg, p, **kw)
        elif options == "prefix":
            InferenceEngine(cfg, p, **kw).register_prefix(list(range(40)))
        elif options == "prefix_from_slot":
            InferenceEngine(cfg, p, **kw).register_prefix_from_slot(
                0, list(range(40)))
        elif options == "warm_prefix":
            InferenceEngine(cfg, p, **kw).warmup(prefix_build=True)
        elif options == "auto_prefix_chat":
            from runbooks_tpu.serve.api import create_server

            class Tok:      # create_server only hands it on
                vocab_size = 512

            create_server(cfg, p, tokenizer=Tok(), auto_prefix_chat=True,
                          warmup=False, **kw)
        else:
            InferenceEngine(cfg, p, **kw, **options)
