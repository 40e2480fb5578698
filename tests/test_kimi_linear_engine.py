"""The cache and the engine under `debug-kimi-linear`
(tests/test_kimi_linear.py has the model against its reference and says
what the tolerances mean): prefill then decode through the three leaves a
row keeps side by side (the KDA state, the conv tail, the latent), prompts
of unequal length in one bucket, a parked row, a slot taken by a second
request; the engine's gauges, counters and refusals for the combination. A
file of its own so that the suite's workers share the load."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.transformer import KVCache, forward
from tests.test_kimi_linear import (
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


@pytest.fixture(scope="module")
def warmed():
    """One engine, built and warmed once (two slots of 64 under seed 3),
    under the tests' own matmul precision, which a compiled program is kept
    by; its steady claim is released at once."""
    from runbooks_tpu.serve.engine import InferenceEngine

    cfg = toy()
    with jax.default_matmul_precision("highest"):
        eng = InferenceEngine(cfg, seeded(cfg, 3), max_slots=2,
                              max_seq_len=64, decode_chunk=4)
        eng.warmup()
    eng.release_steady()
    return eng


def test_prefill_then_decode_through_the_cache_matches_reference():
    """Two rows prefilled in one padded call (position-scatter mode,
    padding parked and masked), 40 and 100 tokens; then both decode a token
    at a time to 60 and 112. Every logit equals the reference's one
    forward. Halfway the long row is parked for three steps: its state,
    its conv tail and its latent rows do not move."""
    cfg = toy()
    p = seeded(cfg, 3)
    n_pre, n_end = [40, 100], [60, 112]
    seqs = [tokens_for(cfg, n, n) for n in n_end]
    want = [reference_logits(cfg, 3, s) for s in seqs]
    max_len, bucket = 128, 128
    cache = KVCache.create(cfg, 2, max_len, trash_slot=True)
    toks = np.zeros((2, bucket), np.int32)
    pos = np.full((2, bucket), max_len, np.int32)
    for r, (s, n) in enumerate(zip(seqs, n_pre)):
        toks[r, :n], pos[r, :n] = s[:n], np.arange(n)
    prefill = jax.jit(lambda c, t, q: forward(
        cfg, p, t, positions=q, cache=c, token_mask=q < max_len,
        row_len_bound=bucket))
    logits, cache = prefill(cache, jnp.asarray(toks), jnp.asarray(pos))
    for r, n in enumerate(n_pre):
        np.testing.assert_allclose(np.asarray(logits[r, :n]), want[r][:n],
                                   atol=TOL)
    step = jax.jit(lambda c, t, q, m: forward(
        cfg, p, t, positions=q, cache=c, cache_view=max_len, token_mask=m))
    at = np.array(n_pre, np.int32)
    for i in range(23):
        alive = np.array([at[0] < n_end[0],
                          at[1] < n_end[1] and not 5 <= i < 8])
        t = np.array([[s[min(a, len(s) - 1)]] for s, a in zip(seqs, at)],
                     np.int32)
        q = np.where(alive, at, max_len).astype(np.int32)
        before = [np.asarray(leaf) for leaf in
                  (cache.state, cache.conv, cache.latent[:, :, :max_len])]
        logits, cache = step(cache, jnp.asarray(t), jnp.asarray(q[:, None]),
                             jnp.asarray(alive[:, None]))
        for r in range(2):
            if alive[r] and at[r] + 1 < n_end[r]:
                np.testing.assert_allclose(np.asarray(logits[r, 0]),
                                           want[r][at[r]], atol=TOL)
            elif not alive[r]:
                after = (cache.state, cache.conv,
                         cache.latent[:, :, :max_len])
                for leaf, was in zip(after, before):
                    np.testing.assert_array_equal(np.asarray(leaf)[:, r],
                                                  was[:, r])
        at = at + alive
    assert at.tolist() == n_end


def test_engine_serves_three_requests_on_two_slots(warmed):
    """A prompt of 30, a prompt of 50 and a third that takes a used slot
    (its state and tail start from zeros, its latent rows from what the
    first left behind, hidden): greedy tokens are the reference's best; the
    gauges say what the three leaves hold."""
    from runbooks_tpu.serve.engine import Request

    cfg, eng = toy(), warmed
    prompts = [tokens_for(cfg, n, n).tolist() for n in (30, 50, 12)]
    budgets = (10, 9, 5)
    reqs = [Request(prompt_tokens=list(q), max_tokens=m, temperature=0.0)
            for q, m in zip(prompts, budgets)]
    eng.generate(reqs)
    for q, r in zip(prompts, reqs):
        seq = np.asarray(q + r.output_tokens, np.int32)
        logits = reference_logits(cfg, 3, seq)
        rows = np.arange(len(q) - 1, len(seq) - 1)
        gap = logits[rows].max(-1) - logits[rows, r.output_tokens]
        assert len(r.output_tokens) == r.max_tokens and gap.max() <= TOL
    occ = eng.kv_occupancy()
    # 7 KDA layers x 2 slots: a state of 4 heads x 32 x 32 float32 and a
    # tail of 3 tokens x 384 channels; 2 latent layers x 2 slots x 65 rows
    # of 64 + 16.
    assert occ["recurrent_state_bytes"] == 7 * 2 * (
        4 * 32 * 32 * 4 + 3 * 384 * 4) \
        == eng.cache.state.nbytes + eng.cache.conv.nbytes
    assert occ["latent_cache_bytes"] == 2 * 2 * 65 * 80 * 4 \
        == eng.cache.latent.nbytes


def test_warmup_compiles_the_programs_the_requests_then_use(warmed):
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.serve.engine import Request

    sentinel = obs_device.SENTINEL
    if not sentinel.install():
        pytest.skip("jax.monitoring unavailable; sentinel cannot verify")
    cfg, eng = toy(), warmed
    census = eng.warmup_census
    assert census["prefill_shapes"] == [list(s) for s in eng.dispatch_shapes]
    total = sentinel.total
    reqs = [Request(prompt_tokens=tokens_for(cfg, n, n).tolist(),
                    max_tokens=m, temperature=0.0)
            for n, m in ((40, 6), (56, 3), (9, 5))]
    eng.generate(reqs)
    assert [len(r.output_tokens) for r in reqs] == [6, 3, 5]
    assert sentinel.total == total, "compiled under traffic"


@pytest.mark.parametrize("options,text", [
    (dict(speculative="ngram"), "speculative decoding.*recurrent"),
    (dict(adapter_pool=2), "adapter pool.*recurrent"),
    ("paged", "kv_paging: paged.*recurrent"),
    ("prefix", "prefix registration.*recurrent"),
    (dict(quantize_kv=True), "quantize_kv.*latent"),
    ("tensor", "tensor mesh axis.*latent"),
])
def test_engine_refusals_name_the_layers(options, text):
    """What the KDA state rules out is refused as for every recurrent
    layer; what only the latent rows rule out (an int8 pool, a tensor
    mesh) by their own name."""
    from runbooks_tpu.serve.engine import InferenceEngine

    cfg = toy()
    p = seeded(cfg, 3)
    kw = dict(max_slots=2, max_seq_len=64)
    with pytest.raises(ValueError, match=text):
        if options == "paged":
            from runbooks_tpu.serve.paging import PagedInferenceEngine

            PagedInferenceEngine(cfg, p, **kw)
        elif options == "prefix":
            InferenceEngine(cfg, p, **kw).register_prefix(list(range(40)))
        elif options == "tensor":
            from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

            InferenceEngine(cfg, p, mesh=make_mesh(
                MeshConfig(tensor=2), devices=jax.devices()[:2]), **kw)
        else:
            InferenceEngine(cfg, p, **kw, **options)
