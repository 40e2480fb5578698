"""Speculative decoding on the chunked decode path (serve/engine.py
make_verify_fn + serve/paging.py make_paged_verify_fn +
serve/speculative.py prompt-lookup drafting).

Correctness bar: greedy outputs must be token-for-token IDENTICAL with
speculation on vs off (dense AND paged, plain AND int8-KV), and
temperature sampling's emitted-token marginal must equal the engine's
own ``sample`` distribution (exact rejection sampling). The drafter is
allowed to be arbitrarily wrong — a bad draft may cost throughput,
never content.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.controller.common import validate_params
from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import init_params
from runbooks_tpu.ops.sampling import sample, speculative_verify
from runbooks_tpu.serve.engine import InferenceEngine, Request
from runbooks_tpu.serve.paging import PagedInferenceEngine
from runbooks_tpu.serve.speculative import NgramDraftIndex


def tiny_cfg(**over):
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                max_seq_len=64, dtype="float32")
    base.update(over)
    return dataclasses.replace(get_config("llama2-7b"), **base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg, jax.random.key(0))


# A prompt with internal repetition: the trailing n-gram recurs, so the
# prompt-lookup drafter fires from the first decode step.
REP_PROMPT = [5, 6, 7, 8] * 5 + [5, 6]
RND_PROMPT = list(np.random.default_rng(7).integers(1, 128, 18))


def drive(engine, reqs, max_steps=800):
    """Step until every (already submitted) request finishes."""
    for _ in range(max_steps):
        engine.step()
        if all(r.finished for r in reqs):
            return
    raise AssertionError("requests did not finish")


def run_all(engine, reqs, max_steps=800):
    for r in reqs:
        engine.submit(r)
    drive(engine, reqs, max_steps)


def greedy_reqs(prompts, max_tokens=12, **kw):
    return [Request(prompt_tokens=list(p), max_tokens=max_tokens,
                    temperature=0.0, **kw) for p in prompts]


# ---------------------------------------------------------------------------
# Prompt-lookup index
# ---------------------------------------------------------------------------

def test_ngram_index_basics():
    idx = NgramDraftIndex(2, ngram_max=3, ngram_min=1)
    idx.begin(0, [1, 2, 3, 4, 1, 2, 3])
    # trailing 3-gram [1,2,3] occurred at 0..2; continuation starts at 3
    assert idx.draft(0, 4) == [4, 1, 2, 3]
    assert idx.draft(0, 2) == [4, 1]
    # longer n wins over a shorter-n match elsewhere
    idx2 = NgramDraftIndex(1, ngram_max=2, ngram_min=1)
    idx2.begin(0, [9, 1, 2, 7, 1, 2])
    assert idx2.draft(0, 1) == [7]        # 2-gram [1,2] -> 7
    # extend shifts the trailing gram; generated tokens are indexed too
    idx2.extend(0, 7)                     # ctx ...1,2,7 ; [2,7] known -> 1
    assert idx2.draft(0, 2) == [1, 2]
    # no match -> empty draft
    idx3 = NgramDraftIndex(1, ngram_max=3, ngram_min=2)
    idx3.begin(0, [1, 2, 3, 4, 5])
    assert idx3.draft(0, 4) == []
    idx.clear(0)
    assert idx.draft(0, 4) == []


def test_ngram_index_trailing_gram_never_matches_itself():
    # Registration is delayed one token: the trailing unigram [3] must
    # not "match" its own occurrence at the end (which would propose an
    # empty continuation); only the earlier occurrence counts.
    idx = NgramDraftIndex(1, ngram_max=1, ngram_min=1)
    idx.begin(0, [3, 9, 3])
    assert idx.draft(0, 2) == [9, 3]
    # a token seen only at the very end has no known continuation yet
    idx.begin(0, [1, 2, 3])
    assert idx.draft(0, 2) == []
    assert idx.context_len(0) == 3


def test_ngram_index_validation():
    with pytest.raises(ValueError, match="ngram"):
        NgramDraftIndex(1, ngram_max=2, ngram_min=3)
    with pytest.raises(ValueError, match="ngram"):
        NgramDraftIndex(1, ngram_max=0, ngram_min=0)


# ---------------------------------------------------------------------------
# Verify-sampling math (ops/sampling.speculative_verify)
# ---------------------------------------------------------------------------

def test_speculative_verify_greedy_math():
    logits = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 3, 16)).astype(np.float32))
    argmax = np.asarray(jnp.argmax(logits, axis=-1))
    # row 0 drafts the exact argmax chain; row 1 drafts wrong tokens
    drafts = np.zeros((2, 2), np.int32)
    drafts[0] = argmax[0, :2]
    drafts[1] = (argmax[1, :2] + 1) % 16
    accept, resid, full = speculative_verify(
        logits, jnp.asarray(drafts), jax.random.key(0),
        jnp.zeros(2), jnp.zeros(2, jnp.int32), jnp.ones(2))
    accept, resid, full = (np.asarray(accept), np.asarray(resid),
                           np.asarray(full))
    assert accept[0].all() and not accept[1].any()
    # greedy correction/bonus are the argmax everywhere
    np.testing.assert_array_equal(resid, argmax[:, :2])
    np.testing.assert_array_equal(full, argmax)


def test_speculative_verify_temperature_marginal_matches_sample():
    """Distribution exactness: the emitted token at a verify position
    (accepted draft, else residual) must be distributed exactly like a
    plain sample() draw — including top-k lane truncation."""
    vocab, n = 12, 4000
    logits = jnp.asarray(
        np.random.default_rng(1).normal(size=(1, 2, vocab))
        .astype(np.float32))
    draft = jnp.asarray([[3]], jnp.int32)   # a mid-probability token
    temps = jnp.asarray([0.9])
    top_ks = jnp.asarray([6], jnp.int32)
    top_ps = jnp.asarray([1.0])
    keys = jax.random.split(jax.random.key(2), n)

    @jax.jit
    def one(key):
        accept, resid, _ = speculative_verify(
            logits, draft, key, temps, top_ks, top_ps)
        return jnp.where(accept[0, 0], draft[0, 0], resid[0, 0])

    emitted = np.asarray(jax.vmap(one)(keys))

    @jax.jit
    def ref(key):
        return sample(logits[:, 0], key, temps, top_ks, top_ps)[0]

    reference = np.asarray(jax.vmap(ref)(jax.random.split(
        jax.random.key(3), n)))
    emp = np.bincount(emitted, minlength=vocab) / n
    exp = np.bincount(reference, minlength=vocab) / n
    # both are n-sample empirical draws from the same distribution
    assert np.abs(emp - exp).max() < 0.05, (emp, exp)
    # tokens outside the top-6 lane must never be emitted
    lane = set(np.asarray(jax.lax.top_k(logits[0, 0], 6)[1]).tolist())
    assert set(np.unique(emitted)).issubset(lane)


def test_speculative_verify_accept_probability_is_pi_draft():
    vocab, n = 8, 4000
    logits = jnp.asarray(
        np.random.default_rng(4).normal(size=(1, 2, vocab))
        .astype(np.float32))
    temp = 0.7
    pi = np.asarray(jax.nn.softmax(logits[0, 0] / temp))
    draft = jnp.asarray([[int(np.argsort(pi)[-2])]], jnp.int32)
    keys = jax.random.split(jax.random.key(5), n)

    @jax.jit
    def one(key):
        accept, _, _ = speculative_verify(
            logits, draft, key, jnp.asarray([temp]),
            jnp.zeros(1, jnp.int32), jnp.ones(1))
        return accept[0, 0]

    rate = float(np.asarray(jax.vmap(one)(keys)).mean())
    assert abs(rate - pi[int(draft[0, 0])]) < 0.04


# ---------------------------------------------------------------------------
# Greedy parity: speculation must never change greedy output
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantize_kv", [False, True],
                         ids=["kv-native", "kv-int8"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_greedy_parity_dense(dtype, quantize_kv):
    cfg = tiny_cfg(dtype=dtype)
    params = init_params(cfg, jax.random.key(0))
    prompts = [REP_PROMPT, RND_PROMPT]
    off = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                          quantize_kv=quantize_kv, speculative="off")
    reqs_off = greedy_reqs(prompts)
    run_all(off, reqs_off)
    on = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                         quantize_kv=quantize_kv, speculative="ngram")
    reqs_on = greedy_reqs(prompts)
    run_all(on, reqs_on)
    assert [r.output_tokens for r in reqs_on] == \
        [r.output_tokens for r in reqs_off]
    # speculation actually fired (the repetitive prompt drafts)
    assert on.spec_drafted > 0
    assert off.spec_drafted == 0 and off.spec_verify_steps == 0


@pytest.mark.parametrize("quantize_kv", [False, True],
                         ids=["kv-native", "kv-int8"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_greedy_parity_paged(dtype, quantize_kv):
    cfg = tiny_cfg(dtype=dtype)
    params = init_params(cfg, jax.random.key(0))
    prompts = [REP_PROMPT, RND_PROMPT]
    off = PagedInferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                               page_size=8, quantize_kv=quantize_kv,
                               speculative="off")
    reqs_off = greedy_reqs(prompts)
    run_all(off, reqs_off)
    on = PagedInferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                              page_size=8, quantize_kv=quantize_kv,
                              speculative="ngram")
    reqs_on = greedy_reqs(prompts)
    run_all(on, reqs_on)
    assert [r.output_tokens for r in reqs_on] == \
        [r.output_tokens for r in reqs_off]
    assert on.spec_drafted > 0


# ---------------------------------------------------------------------------
# Batched verify semantics
# ---------------------------------------------------------------------------

class _OracleEngine(InferenceEngine):
    """Real verify path, controlled drafts: each request carries its own
    future (recorded spec-off greedy output) and a per-request accuracy;
    corrupted tokens always differ from the truth, so they are always
    rejected — accept lengths become deterministic per slot."""

    def _draft_for(self, slot, max_tokens):
        req = self.slot_req[slot]
        done = len(req.output_tokens)
        future = req._oracle[done:done + max_tokens]
        out = []
        for j, t in enumerate(future):
            if req._wrong_at is not None and done + j >= req._wrong_at:
                out.append((int(t) + 1) % self.cfg.vocab_size)
            else:
                out.append(int(t))
        return out


def test_variable_accept_lengths_in_one_batch(model):
    cfg, params = model
    prompts = [REP_PROMPT, RND_PROMPT, list(RND_PROMPT[::-1])]
    off = InferenceEngine(cfg, params, max_slots=4, max_seq_len=64,
                          speculative="off")
    reqs_off = greedy_reqs(prompts)
    run_all(off, reqs_off)
    truth = [r.output_tokens for r in reqs_off]

    on = _OracleEngine(cfg, params, max_slots=4, max_seq_len=64,
                       speculative="ngram", draft_tokens=4,
                       prefill_budget=256)
    reqs_on = greedy_reqs(prompts)
    # slot 0: perfect drafts; slot 1: first draft right then wrong;
    # slot 2: immediately rejected — three different accept lengths in
    # ONE verify dispatch (prefill_budget raised so one step admits all
    # three).
    for r, t, wrong in zip(reqs_on, truth, (None, 2, 0)):
        r._oracle, r._wrong_at = t, wrong
    for r in reqs_on:
        on.submit(r)
    on.step()   # admits all three, then runs one verify step
    lens = [len(r.output_tokens) for r in reqs_on]
    # prefill token + (accepted + 1): full accept = 1+5, reject-at-1 =
    # 1+2, reject-at-0 = 1+1
    assert lens == [6, 3, 2], lens
    drive(on, reqs_on)
    assert [r.output_tokens for r in reqs_on] == truth
    assert 0 < on.spec_accepted < on.spec_drafted


def test_no_draft_slots_ride_the_same_verify_batch(model):
    cfg, params = model
    on = _OracleEngine(cfg, params, max_slots=4, max_seq_len=64,
                       speculative="ngram", draft_tokens=4)
    off = InferenceEngine(cfg, params, max_slots=4, max_seq_len=64)
    reqs_off = greedy_reqs([REP_PROMPT, RND_PROMPT])
    run_all(off, reqs_off)
    truth = [r.output_tokens for r in reqs_off]
    reqs_on = greedy_reqs([REP_PROMPT, RND_PROMPT])
    reqs_on[0]._oracle, reqs_on[0]._wrong_at = truth[0], None
    reqs_on[1]._oracle, reqs_on[1]._wrong_at = [], None  # never drafts
    for r in reqs_on:
        on.submit(r)
    steps_before = on.spec_verify_steps
    on.step()
    # one verify step advanced BOTH slots: the drafting slot by 5, the
    # draft-less one by its plain 1 token (mixed traffic, one program)
    assert on.spec_verify_steps == steps_before + 1
    assert len(reqs_on[0].output_tokens) == 6
    assert len(reqs_on[1].output_tokens) == 2
    drive(on, reqs_on)
    assert [r.output_tokens for r in reqs_on] == truth


def test_all_slots_draftless_falls_back_to_decode_chunk(model):
    cfg, params = model
    on = _OracleEngine(cfg, params, max_slots=2, max_seq_len=64,
                       speculative="ngram", draft_tokens=4)
    reqs = greedy_reqs([RND_PROMPT])
    reqs[0]._oracle, reqs[0]._wrong_at = [], None
    run_all(on, reqs)
    # no drafts anywhere -> every step was a plain decode chunk
    assert on.spec_verify_steps == 0 and on.spec_drafted == 0
    off = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64)
    reqs_off = greedy_reqs([RND_PROMPT])
    run_all(off, reqs_off)
    assert reqs[0].output_tokens == reqs_off[0].output_tokens


# ---------------------------------------------------------------------------
# Paged rollback / radix safety
# ---------------------------------------------------------------------------

class _PagedOracleEngine(PagedInferenceEngine):
    _draft_for = _OracleEngine._draft_for


def test_paged_rollback_never_corrupts_shared_pages(model):
    """Rejected-draft rollback with radix-shared prefix pages in play:
    every write must land in private pages, so followers reusing the
    shared prefix (and pages adopted from speculative finishers) decode
    the exact spec-off tokens, and page accounting balances."""
    cfg, params = model
    shared = list(range(1, 17))          # 2 full 8-token pages
    prompts = [shared + [50 + i] for i in range(3)]

    off = PagedInferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                               page_size=8, speculative="off")
    off.register_prefix(shared)
    reqs_off = greedy_reqs(prompts, max_tokens=10)
    run_all(off, reqs_off)
    truth = [r.output_tokens for r in reqs_off]

    on = _PagedOracleEngine(cfg, params, max_slots=2, max_seq_len=64,
                            page_size=8, speculative="ngram",
                            draft_tokens=4)
    on.register_prefix(shared)
    # heavy rejection traffic: every slot's drafts go wrong at token 2
    reqs_on = greedy_reqs(prompts, max_tokens=10)
    for r, t in zip(reqs_on, truth):
        r._oracle, r._wrong_at = t, 2
    run_all(on, reqs_on)
    assert [r.output_tokens for r in reqs_on] == truth
    assert 0 < on.spec_accepted < on.spec_drafted   # rejections happened
    # radix parity after rejection: a FOLLOWER admitted against the
    # tree state left by speculative finishers still matches greedy
    follower = greedy_reqs([shared + [50]], max_tokens=10)
    follower[0]._oracle, follower[0]._wrong_at = truth[0], 2
    run_all(on, follower)
    assert follower[0].output_tokens == truth[0]
    # page accounting balances: all slots free, remaining used pages
    # are exactly the radix tree's (refcount 1 each)
    occ = on.pager.occupancy()
    assert not on.active.any()
    assert occ["pages_used"] == occ["pages_shared"] == on.pager.radix.nodes
    for pages in on.pager.slot_pages:
        assert pages == []


def test_deadline_expiry_with_speculation_releases_pages(model):
    cfg, params = model
    probe = PagedInferenceEngine(cfg, params, max_slots=2,
                                 max_seq_len=64, page_size=8)
    truth = greedy_reqs([REP_PROMPT], max_tokens=30)
    run_all(probe, truth)
    on = _PagedOracleEngine(cfg, params, max_slots=2, max_seq_len=64,
                            page_size=8, speculative="ngram")
    free0 = on.pager.allocator.free_count
    req = Request(prompt_tokens=list(REP_PROMPT), max_tokens=30,
                  temperature=0.0, deadline_s=0.05)
    req._oracle, req._wrong_at = truth[0].output_tokens, None
    on.submit(req)
    on.step()                      # admit + first verify step
    assert on.spec_verify_steps >= 1 and not req.finished
    time.sleep(0.06)
    on.step()                      # deadline check runs between steps
    assert req.finished and req.finish_reason == "deadline"
    # pages released; whatever the tree adopted is tree-only (refcount 1)
    assert on.pager.slot_pages[req._slot if req._slot >= 0 else 0] == []
    assert on.pager.allocator.free_count == \
        free0 - on.pager.radix.nodes


def test_eos_inside_accepted_draft(model):
    cfg, params = model
    off = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64)
    probe = greedy_reqs([REP_PROMPT], max_tokens=12)
    run_all(off, probe)
    # pick an EOS that FIRST appears mid-output, so with K=4 drafting it
    # sits INSIDE an accepted draft run (a token that also opens the
    # output would end the request at the prefill's token, before any
    # draft is verified: this model repeats its first token nine times)
    out = probe[0].output_tokens
    eos = next(t for i, t in enumerate(out) if i >= 2 and t not in out[:i])
    reqs_off = greedy_reqs([REP_PROMPT], max_tokens=12, eos_id=eos)
    run_all(off, reqs_off)
    on = _OracleEngine(cfg, params, max_slots=2, max_seq_len=64,
                       speculative="ngram", draft_tokens=4)
    reqs_on = greedy_reqs([REP_PROMPT], max_tokens=12, eos_id=eos)
    reqs_on[0]._oracle, reqs_on[0]._wrong_at = probe[0].output_tokens, None
    run_all(on, reqs_on)
    assert reqs_on[0].output_tokens == reqs_off[0].output_tokens
    assert reqs_on[0].finish_reason == reqs_off[0].finish_reason == "stop"
    assert reqs_on[0].output_tokens[-1] == eos
    assert on.spec_accepted > 0


def test_draft_caps_respect_budget_and_room(model):
    cfg, params = model
    on = _OracleEngine(cfg, params, max_slots=2, max_seq_len=64,
                       speculative="ngram", draft_tokens=4)
    off = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64)
    reqs_off = greedy_reqs([REP_PROMPT], max_tokens=3)
    run_all(off, reqs_off)
    # max_tokens=3: after the prefill token only 2 remain, so the cap is
    # 1 draft (emitting d+1 <= remaining); output must not overshoot
    reqs_on = greedy_reqs([REP_PROMPT], max_tokens=3)
    reqs_on[0]._oracle, reqs_on[0]._wrong_at = reqs_off[0].output_tokens, \
        None
    run_all(on, reqs_on)
    assert reqs_on[0].output_tokens == reqs_off[0].output_tokens
    assert len(reqs_on[0].output_tokens) == 3
    assert reqs_on[0].finish_reason == "length"


# ---------------------------------------------------------------------------
# Compile discipline + observability
# ---------------------------------------------------------------------------

def test_zero_unexpected_compiles_in_steady_speculative_loop(model):
    from runbooks_tpu.obs import device as obs_device

    cfg, params = model
    engine = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                             speculative="ngram")
    try:
        engine.warmup()
        assert engine.warmup_census["verify_programs"] == \
            len(engine.view_buckets)
        assert engine.warmup_census["speculative"] == "ngram"
        sentinel = obs_device.SENTINEL
        before = sentinel.unexpected
        # steady traffic across both paths: drafting slots (verify) and
        # draft-less slots (plain chunk), several admission waves
        for _ in range(2):
            reqs = greedy_reqs([REP_PROMPT, RND_PROMPT], max_tokens=10)
            run_all(engine, reqs)
        assert engine.spec_verify_steps > 0
        assert sentinel.unexpected == before, \
            sentinel.recent_unexpected()
    finally:
        engine.release_steady()


def test_spec_metrics_and_stats(model):
    from runbooks_tpu.obs import metrics as obs_metrics

    cfg, params = model
    engine = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                             speculative="ngram")
    run_all(engine, greedy_reqs([REP_PROMPT], max_tokens=10))
    stats = engine.spec_stats()
    assert stats["mode"] == "ngram"
    assert stats["drafted_total"] == engine.spec_drafted > 0
    assert stats["accepted_total"] == engine.spec_accepted
    assert 0.0 <= stats["accept_rate"] <= 1.0
    buckets = stats["tokens_per_sec_by_accept_rate"]
    assert set(buckets) == {"0-25%", "25-50%", "50-75%", "75-100%"}
    assert sum(b["tokens"] for b in buckets.values()) > 0
    # the engine-side histograms exist in the process registry
    text = obs_metrics.REGISTRY.render()
    assert "serve_spec_accept_len_bucket" in text
    assert "serve_verify_dispatch_seconds_bucket" in text
    # spec-off engines report a bare mode and register no spec families
    off = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64)
    assert off.spec_stats() == {"mode": "off"}


# ---------------------------------------------------------------------------
# Validation (engine + controller)
# ---------------------------------------------------------------------------

def test_engine_speculative_validation(model):
    cfg, params = model
    with pytest.raises(ValueError, match="speculative"):
        InferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                        speculative="medusa")
    with pytest.raises(ValueError, match="draft_tokens"):
        InferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                        speculative="ngram", draft_tokens=0)
    with pytest.raises(ValueError, match="ngram"):
        InferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                        speculative="ngram", ngram_max=1, ngram_min=2)
    # the engine holds the record it was given, and serves from it
    eng = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64,
                          speculative="ngram", draft_tokens=2)
    assert eng.options.speculative == "ngram" and eng.draft_tokens == 2
    assert eng._spec_index is not None


def test_validate_params_speculative():
    assert validate_params({"speculative": "ngram"}) is None
    assert validate_params({"speculative": "off"}) is None
    err = validate_params({"speculative": "medusa"})
    assert err is not None and "speculative" in err
    err = validate_params({"draft_tokens": 0})
    assert err is not None and "draft_tokens" in err
    err = validate_params({"draftTokens": "four"})
    assert err is not None
    assert validate_params({"draftTokens": 8, "ngramMax": 4,
                            "ngramMin": 2}) is None
    err = validate_params({"ngram_min": 3, "ngram_max": 2})
    assert err is not None and "ngram_min" in err
    # a lone ngram_min above the engine default ngram_max (3) must fail
    # HERE, not crash-loop the replica at engine construction
    err = validate_params({"ngram_min": 5})
    assert err is not None and "ngram_min" in err
    assert validate_params({"ngram_min": 3}) is None
    assert validate_params({"ngram_max": 1}) is None  # default min is 1
    err = validate_params({"ngramMin": 0})
    assert err is not None
