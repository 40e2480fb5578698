"""The slot engine with recurrent state beside the KV cache: what it serves
against the benchmark's plain reference, the invariant of
serve/engine.make_prefill_fn rule by rule, and the refusals."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.transformer import KVCache, flash_heads_per_step
from runbooks_tpu.serve.engine import (
    InferenceEngine,
    Request,
    make_decode_fn,
    make_prefill_fn,
)
from tests.hybrid_fixture import (
    AS_RUN,
    load_reference,
    seeded_params,
    tiny_config,
)

SEED = 9


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config(dtype="float32", param_dtype="bfloat16")
    return cfg, seeded_params(cfg, SEED)


@pytest.fixture(scope="module")
def reference():
    ref = load_reference()
    return ref, ref.init_weights(AS_RUN, SEED)


def served_gap(reference, req) -> float:
    """The checker's number: the widest gap by which a served token's logit
    lies below the reference's best at its position (full forward over
    prompt + served tokens: no cache, no chunks, no batch)."""
    ref, w = reference
    toks = req.prompt_tokens + req.output_tokens
    rows = np.arange(len(req.prompt_tokens) - 1, len(toks) - 1)
    logits = np.asarray(ref.logits_at(AS_RUN, w, toks, rows))
    served = logits[np.arange(len(rows)), np.asarray(req.output_tokens)]
    return float((logits.max(-1) - served).max())


def requests(spec, seed=1):
    rng = np.random.default_rng(seed)
    return [Request(prompt_tokens=rng.integers(1, 512, n).tolist(),
                    max_tokens=m) for n, m in spec]


# The weights are the bfloat16 ones the reference holds and the program
# computes in float32, so both sides compute the same model; what is left
# is float32 round-off:
# a served token that is not the reference's best lies below it by no more
# than the two disagree (logits of order 1, relative error of order 1e-5).
GAP_LIMIT = 2e-4


def test_flash_prefill_counts_the_blocks_it_visits(reference):
    """A prompt shorter than its bucket through the flash forward: /metrics
    shows fewer blocks visited than the grid has, with the numbers
    block_ranges gives for the positions the kernel was handed, and what
    the engine then serves (prefill, then decode) is still the uncached
    forward's."""
    from runbooks_tpu.obs.metrics import REGISTRY
    from runbooks_tpu.ops.flash_attention import block_counts

    cfg = tiny_config(dtype="float32", param_dtype="bfloat16",
                      attention_impl="flash", flash_block_q=16,
                      flash_block_k=32)
    eng = InferenceEngine(cfg, seeded_params(cfg, SEED), max_slots=2,
                          max_seq_len=128)
    (req,) = requests([(40, 6)])
    bucket = eng._bucket_for(40)
    assert bucket == 64
    names = ("serve_flash_blocks_visited_total",
             "serve_flash_blocks_grid_total")
    before = [REGISTRY.counter_value(n, bucket="64") for n in names]
    eng.generate([req])
    visited, grid = (REGISTRY.counter_value(n, bucket="64") - b
                     for n, b in zip(names, before))
    # Query blocks of 16 rows: positions 0-15 and 16-31 see kv block 0,
    # 32-39 (and 8 parked rows) blocks 0-1, the all-parked fourth none;
    # the grid is 4 x ceil(129 / 32).
    q_pos = np.full((1, bucket), -1, np.int32)
    q_pos[0, :40] = np.arange(40)
    kv_pos = np.arange(129, dtype=np.int32)[None]
    assert (visited, grid) == block_counts(q_pos, kv_pos, None, None, 16, 32,
                                           True) == (4, 20)
    text = REGISTRY.render()
    assert all(f'{n}{{bucket="64"}}' in text for n in names)
    assert len(req.output_tokens) == 6
    assert served_gap(reference, req) < GAP_LIMIT


@pytest.mark.parametrize("given", [(16, 32), (32, None), (None, None)],
                         ids=["pinned", "half pinned", "from the shapes"])
def test_flash_counters_are_taken_at_the_compiled_block_shape(monkeypatch,
                                                              given):
    """What _count_flash_blocks bumps is block_counts at the shape the
    prefill program was traced with, which is what the engine publishes
    (flash_blocks): a configuration's sizes where a test pins them, else
    ops/flash_attention.block_shape's answer for the program's shapes (a
    bucket of 64 on 129 cache slots: one block of each)."""
    import runbooks_tpu.ops.flash_attention as fa
    from runbooks_tpu.obs.metrics import REGISTRY

    cfg = tiny_config(dtype="float32", param_dtype="bfloat16",
                      attention_impl="flash", flash_block_q=given[0],
                      flash_block_k=given[1])
    traced, answer = [], fa.blocks_of_call
    monkeypatch.setattr(fa, "blocks_of_call", lambda *a, **kw: traced.append(
        answer(*a, **kw)) or traced[-1])
    eng = InferenceEngine(cfg, seeded_params(cfg, SEED), max_slots=2,
                          max_seq_len=128)
    (req,) = requests([(40, 2)])
    names = ("serve_flash_blocks_visited_total",
             "serve_flash_blocks_grid_total")
    before = [REGISTRY.counter_value(n, bucket="64") for n in names]
    eng.generate([req])
    counted = tuple(REGISTRY.counter_value(n, bucket="64") - b
                    for n, b in zip(names, before))
    blocks = eng.flash_blocks["prefill_b64"]["full_attention"]["fwd"]
    assert blocks == [given[0] or 64, given[1] or 129]
    assert set(traced) == {tuple(blocks)}
    q_pos = np.full((1, 64), -1, np.int32)
    q_pos[0, :40] = np.arange(40)
    kv_pos = np.arange(129, dtype=np.int32)[None]
    assert counted == fa.block_counts(q_pos, kv_pos, None, None, *blocks,
                                      True)
    assert eng.flash_head_block["prefill_b64"] == flash_heads_per_step(
        cfg, 64, 129)


@pytest.mark.parametrize("chunk", [1, 4])
def test_one_bucket_one_group_then_chunked_decode(model, reference, chunk):
    """Prompts of unequal length in one bucket (64) and one admission
    group, rows that end mid-chunk beside rows that go on."""
    cfg, params = model
    eng = InferenceEngine(cfg, params, max_slots=4, max_seq_len=128,
                          decode_chunk=chunk, prefill_budget=4 * 64)
    reqs = requests([(33, 9), (50, 6), (61, 14), (40, 3)])
    for r in reqs:
        eng.submit(r)
    eng.step()
    # One tick admitted all four as one [4, 64] prefill.
    assert sorted(r._slot for r in reqs) == [0, 1, 2, 3]
    assert all(len(r.output_tokens) >= 1 for r in reqs)
    while eng.has_work():
        eng.step()
    for r in reqs:
        assert len(r.output_tokens) == r.max_tokens and r.finished
        assert served_gap(reference, r) < GAP_LIMIT


def test_a_slot_reused_after_a_longer_occupant(model, reference):
    """Rule (a): the second request starts from zero state, whatever the
    first left in the slot."""
    cfg, params = model
    eng = InferenceEngine(cfg, params, max_slots=1, max_seq_len=128,
                          decode_chunk=4)
    first, second = requests([(90, 12), (19, 8)], seed=2)
    eng.generate([first])
    assert float(jnp.abs(eng.cache.state).max()) > 0
    eng.generate([second])
    assert first._slot == second._slot == 0
    assert served_gap(reference, first) < GAP_LIMIT
    assert served_gap(reference, second) < GAP_LIMIT


def test_mixed_buckets_queue_and_reuse(model, reference):
    cfg, params = model
    eng = InferenceEngine(cfg, params, max_slots=2, max_seq_len=128,
                          decode_chunk=4)
    reqs = requests([(70, 5), (17, 11), (33, 2), (100, 7), (20, 9)], seed=3)
    eng.generate(reqs)
    assert all(r.finished for r in reqs)
    assert max(served_gap(reference, r) for r in reqs) < GAP_LIMIT


def test_cache_view_smaller_than_the_cache(model, reference):
    cfg, params = model
    # max_seq_len 512 gives view buckets 256 and 512: decode reads K/V
    # through the 256 view while the state has no view at all.
    eng = InferenceEngine(cfg, params, max_slots=2, max_seq_len=512,
                          decode_chunk=4)
    assert eng.view_buckets == [256, 512]
    reqs = requests([(60, 10), (45, 6)], seed=4)
    eng.generate(reqs)
    assert max(served_gap(reference, r) for r in reqs) < GAP_LIMIT


def test_parked_rows_and_padding_rows_leave_state_alone(model):
    """Rules (b) and (c) on the programs themselves."""
    cfg, params = model
    slots, max_len = 4, 64
    pool = KVCache.create(cfg, slots, max_len, trash_slot=True)
    rng = np.random.default_rng(5)
    pool.state = jnp.asarray(rng.normal(size=pool.state.shape), jnp.float32)
    pool.conv = jnp.asarray(rng.normal(size=pool.conv.shape), jnp.float32)
    before = jax.tree.map(jnp.copy, pool)
    key = jax.random.key(0)
    # Decode, rows 1 and 3 parked: their state does not move by a bit.
    decode = jax.jit(make_decode_fn(cfg, 4, max_len, max_len, max_len))
    alive = jnp.array([True, False, True, False])
    _, valid, _, after, _ = decode(
        params, pool, jnp.array([5, 6, 7, 8]), jnp.array([10, 0, 3, 0]),
        key, jnp.zeros(4), jnp.zeros(4, jnp.int32), jnp.ones(4),
        jnp.full(4, -1), jnp.array([9, 0, 2, 0]), alive)
    assert valid[:, 0].all() and valid[:2, 2].all() and not valid[2:, 2].any()
    for leaf in ("state", "conv"):
        new, old = getattr(after, leaf), getattr(before, leaf)
        assert jnp.array_equal(new[:, 1], old[:, 1])
        assert jnp.array_equal(new[:, 3], old[:, 3])
        assert not jnp.array_equal(new[:, 0], old[:, 0])
    # Prefill of two real rows in a [4, 16] program into slots 2 and 0:
    # slots 1 and 3 keep what they had; slot 0 gets row 1's state, which
    # started from zero (its conv tail holds its last three inputs only).
    prefill = jax.jit(make_prefill_fn(cfg, max_len + 1))
    tokens = jnp.asarray(rng.integers(1, 512, (4, 16)), jnp.int32)
    lengths = np.array([16, 2, 0, 0])
    pos = np.where(np.arange(16)[None] < lengths[:, None],
                   np.arange(16)[None], max_len)
    _, filled, _ = prefill(
        params, before, tokens, jnp.asarray(pos, jnp.int32),
        jnp.array([2, 0, 2, 2]), jnp.asarray(np.maximum(lengths - 1, 0)),
        key, jnp.zeros(4), jnp.zeros(4, jnp.int32), jnp.ones(4))
    for leaf in ("state", "conv"):
        new, old = getattr(filled, leaf), getattr(before, leaf)
        assert jnp.array_equal(new[:, 1], old[:, 1])
        assert jnp.array_equal(new[:, 3], old[:, 3])
    assert jnp.array_equal(filled.conv[:, 0, 0], jnp.zeros_like(
        filled.conv[:, 0, 0]))      # two tokens: the oldest tap is empty
    alone = jax.jit(make_prefill_fn(cfg, max_len + 1))(
        params, before, tokens[1:2], jnp.asarray(pos[1:2], jnp.int32),
        jnp.array([0]), jnp.array([1]), key, jnp.zeros(1),
        jnp.zeros(1, jnp.int32), jnp.ones(1))[1]
    np.testing.assert_allclose(filled.state[:, 0], alone.state[:, 0],
                               atol=1e-4)


@pytest.mark.parametrize("view", [64, 32], ids=["whole", "short-view"])
@pytest.mark.parametrize("donate", [False, True],
                         ids=["kept", "pool-donated"])
def test_a_row_not_alive_keeps_its_cache_across_a_chunk(model, donate, view):
    """Rule (c) with the leaves carried through both loops and updated in
    place: across a chunk of 8 steps a row that is not alive, or that dies
    on the way, keeps state and conv bit for bit from then on, and its K/V
    everywhere but the trash slot; a live row's state moves every step."""
    cfg, params = model
    slots, max_len, chunk = 4, 64, 8
    rng = np.random.default_rng(6)
    pool = KVCache.create(cfg, slots, max_len, trash_slot=True)
    pool = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype)
        if a.ndim else a, pool)
    before = jax.tree.map(np.asarray, pool)
    decode = jax.jit(make_decode_fn(cfg, chunk, max_len, max_len, view),
                     donate_argnums=(1,) if donate else ())
    # Row 1 is parked from the start; row 2 has two tokens left to emit.
    alive = jnp.array([True, False, True, True])
    args = (jnp.array([5, 6, 7, 8]), jnp.array([10, 0, 3, 20]),
            jax.random.key(0), jnp.zeros(4), jnp.zeros(4, jnp.int32),
            jnp.ones(4), jnp.full(4, -1))
    _, valid, _, after, _ = decode(params, pool, *args,
                                   jnp.array([99, 0, 2, 99]), alive)
    assert np.asarray(valid).sum(0).tolist() == [8, 0, 2, 8]
    for leaf in ("state", "conv"):
        new, old = np.asarray(getattr(after, leaf)), getattr(before, leaf)
        assert np.array_equal(new[:, 1], old[:, 1]), leaf
        assert not np.array_equal(new[:, 0], old[:, 0]), leaf
    for leaf in ("k", "v"):
        new, old = np.asarray(getattr(after, leaf)), getattr(before, leaf)
        assert np.array_equal(new[:, 1, :max_len], old[:, 1, :max_len])
        # Row 0 wrote slots 10..17 and nothing else; row 2 slots 3 and 4.
        for row, lo, hi in ((0, 10, 18), (2, 3, 5)):
            changed = np.flatnonzero(
                (new[:, row, :max_len] != old[:, row, :max_len])
                .any(axis=(0, 2, 3)))
            assert changed.tolist() == list(range(lo, hi)), (leaf, row)
    # Row 2 after it died: the same as a chunk that ends where it died.
    pool2 = jax.tree.map(jnp.asarray, before)
    short = jax.jit(make_decode_fn(cfg, 2, max_len, max_len, view))
    _, _, _, ended, _ = short(params, pool2, *args,
                              jnp.array([99, 0, 2, 99]), alive)
    for leaf in ("state", "conv"):
        assert np.array_equal(np.asarray(getattr(after, leaf))[:, 2],
                              np.asarray(getattr(ended, leaf))[:, 2]), leaf


REFUSALS = [
    (dict(speculative="ngram"), "speculative decoding is not supported"),
    (dict(adapter_pool=2), "an adapter pool is not supported"),
]


@pytest.mark.parametrize("kwargs,message", REFUSALS,
                         ids=["speculation", "adapter_pool"])
def test_engine_refuses_at_construction_with_the_reason(model, kwargs,
                                                        message):
    cfg, params = model
    with pytest.raises(ValueError, match=message) as err:
        InferenceEngine(cfg, params, max_slots=2, max_seq_len=64, **kwargs)
    assert "recurrent (linear-attention) layers" in str(err.value)


def test_paged_engine_refuses_recurrent_state(model):
    from runbooks_tpu.serve.paging import PagedInferenceEngine

    cfg, params = model
    with pytest.raises(ValueError, match="kv_paging: paged is not supported"):
        PagedInferenceEngine(cfg, params, max_slots=2, max_seq_len=64)


@pytest.mark.parametrize("call", ["register_prefix",
                                  "register_prefix_from_slot",
                                  "warmup_prefix_build"])
def test_prefix_registration_is_refused(model, call):
    cfg, params = model
    eng = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64)
    with pytest.raises(ValueError, match="prefix registration"):
        if call == "register_prefix":
            eng.register_prefix(list(range(1, 40)))
        elif call == "register_prefix_from_slot":
            eng.register_prefix_from_slot(0, list(range(1, 40)))
        else:
            eng.warmup(prefix_build=True)


def test_server_refuses_auto_prefix_chat_and_reports_state_bytes(model):
    from runbooks_tpu.serve.api import create_server

    cfg, params = model

    class Tok:      # create_server only hands it on
        vocab_size = 512

    with pytest.raises(ValueError, match="auto_prefix_chat"):
        create_server(cfg, params, tokenizer=Tok(), max_slots=2,
                      max_seq_len=64, auto_prefix_chat=True, warmup=False)
    with pytest.raises(ValueError, match="kv_paging: paged"):
        create_server(cfg, params, tokenizer=Tok(), max_slots=2,
                      max_seq_len=64, kv_paging=True, warmup=False)
    eng = InferenceEngine(cfg, params, max_slots=2, max_seq_len=64)
    occ = eng.kv_occupancy()
    # 6 linear layers x 2 slots x (4 x 32 x 64 f32 + 3 x 512 f32).
    assert occ["recurrent_state_bytes"] == 6 * 2 * (
        4 * 32 * 64 + 3 * 512) * 4
    assert occ["kv_pool_bytes"] == 2 * eng.cache.k.nbytes
    groups = eng.memory_groups()
    assert groups["kv_cache"].state is None
    assert groups["recurrent_state"][0] is eng.cache.state
    plain = InferenceEngine(*plain_model(), max_slots=2, max_seq_len=64)
    assert plain.kv_occupancy()["recurrent_state_bytes"] == 0
    assert "recurrent_state" not in plain.memory_groups()


def plain_model():
    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params

    cfg = get_config("debug")
    return cfg, init_params(cfg, jax.random.key(0))
