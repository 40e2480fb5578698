"""The cache and the engine under `debug-minicpm-sala`
(tests/test_minicpm_sala.py has the model against its reference and says
what the tolerances mean): prefill then decode through the K/V leaves, the
compressed-key leaf and the lightning state, a short row that crosses
dense_len while it decodes beside a long one in one bucket, a parked row, a
slot taken by a second request; the warm-up rule (what is compiled is what
admission can dispatch); the engine's counters, gauges and refusals. A file
of its own so that the suite's workers share the load."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.transformer import KVCache, forward
from tests.test_minicpm_sala import (
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
    """One engine, built and warmed once, for the tests that only send it
    requests (two slots of 128 under seed 3). Warmed under the tests' own
    matmul precision, which a compiled program is kept by; its steady
    claim is released at once, so that what other tests compile is not
    counted against it."""
    from runbooks_tpu.serve.engine import InferenceEngine

    cfg = toy()
    with jax.default_matmul_precision("highest"):
        eng = InferenceEngine(cfg, seeded(cfg, 3), max_slots=2,
                              max_seq_len=128, decode_chunk=4)
        eng.warmup()
    eng.release_steady()
    return eng


def test_prefill_then_decode_through_the_cache_matches_reference():
    """Two rows prefilled in one padded call (position-scatter mode,
    padding parked and masked): one of 40 tokens, read densely, one of 100,
    read sparsely. Then both decode a token at a time to 80 and 120: the
    short row crosses dense_len (64) on the way, and from there its NEW
    tokens choose their blocks while the prompt's keys stay what a dense
    prefill made them. Every logit equals the reference's one forward.
    Halfway the long row is parked for three steps: its state and its
    compressed keys do not move. At the end every whole compressed key of
    the leaf is the mean of the row's stored keys."""
    from runbooks_tpu.ops.block_sparse_attention import compress_keys

    cfg = toy()
    p = seeded(cfg, 3)
    n_pre, n_end = [40, 100], [80, 120]
    seqs = [tokens_for(cfg, n, n) for n in n_end]
    want = [reference_logits(cfg, 3, s, n) for s, n in zip(seqs, n_pre)]
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
    for i in range(43):
        alive = np.array([at[0] < n_end[0],
                          at[1] < n_end[1] and not 5 <= i < 8])
        t = np.array([[s[min(a, len(s) - 1)]] for s, a in zip(seqs, at)],
                     np.int32)
        q = np.where(alive, at, max_len).astype(np.int32)
        before = (np.asarray(cache.state), np.asarray(cache.ckeys))
        logits, cache = step(cache, jnp.asarray(t), jnp.asarray(q[:, None]),
                             jnp.asarray(alive[:, None]))
        for r in range(2):
            if alive[r] and at[r] + 1 < n_end[r]:
                np.testing.assert_allclose(np.asarray(logits[r, 0]),
                                           want[r][at[r]], atol=TOL)
            elif not alive[r]:
                for leaf, was in zip((cache.state, cache.ckeys), before):
                    np.testing.assert_array_equal(np.asarray(leaf)[:, r],
                                                  was[:, r])
        at = at + alive
    assert at.tolist() == n_end
    sp = cfg.sparse_read
    for r, n in enumerate(n_end):
        whole = (n - sp.kernel) // sp.stride + 1
        for layer in range(2):
            np.testing.assert_allclose(
                np.asarray(cache.ckeys[layer, r, :whole]),
                np.asarray(compress_keys(cache.k[layer, r:r + 1, :n],
                                         sp)[0]), atol=1e-6)


def test_dispatch_shapes_are_what_two_requests_can_fill():
    from runbooks_tpu.serve.engine import _buckets, dispatch_shapes

    buckets = _buckets(2048)
    shapes = dispatch_shapes(buckets, 2048, 8)
    assert [b for r, b in shapes if r == 1] == buckets
    # [max_slots, b] only where a tick's budget holds two of b.
    assert [b for r, b in shapes if r == 8] == [b for b in buckets
                                                 if 2 * b <= 2048]
    assert (8, 2048) not in shapes and (8, 1024) in shapes
    assert len(dispatch_shapes(buckets, 4096, 8)) == 2 * len(buckets)
    assert dispatch_shapes(buckets, 2048, 1) == [(1, b) for b in buckets]
    # The long cell's: of 11 buckets the [8, 16384] program alone is out.
    long = dispatch_shapes(_buckets(16384), 16384, 8)
    assert len(long) == 21 and (8, 16384) not in long and (8, 8192) in long


def test_admission_dispatches_only_warmed_shapes():
    """_admit driven over a grid of budgets and prompt lengths, with every
    slot free and a full queue: each group it hands to _prefill_group has
    a (rows, bucket) that dispatch_shapes names for that budget."""
    from runbooks_tpu.serve.engine import (
        InferenceEngine,
        Request,
        prefill_rows,
    )

    cfg = toy()
    eng = InferenceEngine(cfg, seeded(cfg, 3), max_slots=4, max_seq_len=128)
    seen = []
    eng._prefill_group = lambda bucket, group, pkey=None: seen.append(
        (prefill_rows(len(group), eng.max_slots), bucket))
    rng = np.random.default_rng(0)
    grouped = 0
    for budget in (16, 48, 64, 100, 128, 256, 512):
        eng.prefill_budget = budget
        warmed = set(eng.dispatch_shapes)
        for lengths in ([9, 9, 9, 9], [30, 31, 17, 5], [64, 64, 3, 64],
                        [100, 120, 100, 120], [128, 16, 16, 16],
                        *rng.integers(1, 129, (12, 4)).tolist()):
            eng.queue = [Request(prompt_tokens=[1] * n, max_tokens=2)
                         for n in lengths]
            del seen[:]
            eng._admit()
            assert seen and set(seen) <= warmed, (budget, lengths, seen)
            grouped += sum(rows > 1 for rows, _ in seen)
            # The first of a tick always goes through, whatever it needs.
            assert len(eng.queue) < 4
    assert grouped > 20     # the grid does fill [max_slots, b] programs


def test_engine_serves_short_and_long_rows_and_counts_the_sparse_read(
        warmed):
    """Three requests on two slots: a prompt of 50 that decodes across
    dense_len, a prompt of 90 (read sparsely from its prefill on), a third
    that takes a used slot. Greedy tokens are the reference's best; the
    gauges and the sparse read's counters say what was held and read."""
    from runbooks_tpu.obs import metrics as obs_metrics
    from runbooks_tpu.serve.engine import Request

    def counter(name, program):
        snap = obs_metrics.REGISTRY.render()
        line = next((ln for ln in snap.splitlines() if ln.startswith(
            f'{name}{{program="{program}"}}')), None)
        return float(line.split()[-1]) if line else 0.0

    cfg, eng = toy(), warmed
    names = ("serve_bsa_pairs_needed_total", "serve_bsa_pairs_visited_total",
             "serve_bsa_blocks_chosen_total")
    base = {(n, pr): counter(n, pr) for n in names
            for pr in ("prefill", "decode")}
    prompts = [tokens_for(cfg, n, n).tolist() for n in (50, 90, 20)]
    budgets = (24, 9, 5)
    reqs = [Request(prompt_tokens=list(q), max_tokens=m, temperature=0.0)
            for q, m in zip(prompts, budgets)]
    eng.generate(reqs)
    for q, r in zip(prompts, reqs):
        seq = np.asarray(q + r.output_tokens, np.int32)
        logits = reference_logits(cfg, 3, seq, len(q))
        rows = np.arange(len(q) - 1, len(seq) - 1)
        gap = logits[rows].max(-1) - logits[rows, r.output_tokens]
        assert len(r.output_tokens) == r.max_tokens and gap.max() <= TOL
    occ = eng.kv_occupancy()
    # 6 lightning layers x 2 slots x 4 heads x 32 x 32 float32, no tail;
    # 2 sparse-read layers x 2 slots x 63 compressed keys x 2 heads x 32.
    assert occ["recurrent_state_bytes"] == 6 * 2 * 4 * 32 * 32 * 4
    assert occ["kv_compressed_bytes"] == 2 * 2 * 63 * 2 * 32 * 4 \
        == eng.cache.ckeys.nbytes
    assert occ["kv_pool_bytes"] == 2 * eng.cache.k.nbytes \
        + occ["kv_compressed_bytes"]
    got = {key: counter(*key) - was for key, was in base.items()}
    for program in ("prefill", "decode"):
        needed = got["serve_bsa_pairs_needed_total", program]
        visited = got["serve_bsa_pairs_visited_total", program]
        assert 0 < needed < visited
        assert got["serve_bsa_blocks_chosen_total", program] > 0
    # Two prefills ran the sparse core's program: the long row (90 tokens,
    # the bucket of 128) and the row of 50 in the bucket of 64, which a
    # row of 64 could fill (read densely, every earlier key needed); the
    # bucket of 32 reads through the dense path and counts nothing. The
    # kernel's one step a bucket computes every query of the bucket
    # against the 129 slots; the long row's choices need fewer pairs than
    # a causal mask's.
    assert got["serve_bsa_pairs_visited_total", "prefill"] \
        == (128 + 64) * 129
    assert 50 * 51 // 2 < got["serve_bsa_pairs_needed_total", "prefill"] \
        < 90 * 91 // 2 + 50 * 51 // 2


def test_warmup_compiles_the_programs_the_requests_then_use(warmed):
    """After the warm-up requests of different buckets (one beyond
    dense_len, one that crosses it), decode chunks of every view and a slot
    that changes hands compile nothing; the census names the shapes, and
    the largest bucket has no [max_slots, b] program."""
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.serve.engine import Request

    sentinel = obs_device.SENTINEL
    if not sentinel.install():
        pytest.skip("jax.monitoring unavailable; sentinel cannot verify")
    cfg, eng = toy(), warmed
    census = eng.warmup_census
    assert census["prefill_shapes"] == [list(s) for s in eng.dispatch_shapes]
    assert [2, 128] not in census["prefill_shapes"] \
        and [2, 64] in census["prefill_shapes"] \
        and census["prefill_programs"] == 2 * len(eng.prefill_buckets) - 1
    # The buckets under dense_len read through the flash forward (on a
    # TPU); the sparse core's programs are not among its census.
    assert not any(name in eng.flash_blocks
                   for name in ("prefill_b64", "prefill_b128"))
    total = sentinel.total
    reqs = [Request(prompt_tokens=tokens_for(cfg, n, n).tolist(),
                    max_tokens=m, temperature=0.0)
            for n, m in ((60, 8), (100, 3), (9, 5), (70, 2))]
    eng.generate(reqs)
    assert [len(r.output_tokens) for r in reqs] == [8, 3, 5, 2]
    assert sentinel.total == total, "compiled under traffic"


@pytest.mark.parametrize("options,text", [
    (dict(speculative="ngram"), "speculative decoding.*recurrent"),
    (dict(adapter_pool=2), "adapter pool.*recurrent"),
    ("paged", "kv_paging: paged.*recurrent"),
    ("prefix", "prefix registration.*recurrent"),
    (dict(quantize_kv=True), "quantize_kv.*sparse-read"),
    ("tensor", "tensor mesh axis.*sparse-read"),
])
def test_engine_refusals_name_the_layers(options, text):
    """What the lightning state rules out is refused as for every
    recurrent layer; what only the sparse read rules out (an int8 pool, a
    tensor mesh) by its own name."""
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


def test_a_sparse_read_alone_is_refused_by_its_own_name():
    """A model with sparse-read layers and NO recurrent ones: the typed
    refusals are the compressed keys'."""
    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import init_params
    from runbooks_tpu.serve.engine import _REFUSED, InferenceEngine

    cfg = get_config("debug", sparse_block=8, sparse_topk=4, sparse_window=16,
                     sparse_init_blocks=1, sparse_kernel=4, sparse_stride=2,
                     sparse_dense_len=64)
    p = jax.jit(lambda key: init_params(cfg, key))(jax.random.key(0))
    for feature in ("speculative decoding", "an adapter pool",
                    "kv_paging: paged", "quantize_kv",
                    "a tensor mesh axis > 1"):
        assert (feature, "kv_compressed") in _REFUSED
    with pytest.raises(ValueError, match="speculative.*sparse-read"):
        InferenceEngine(cfg, p, max_slots=2, max_seq_len=64,
                        speculative="ngram")
    with pytest.raises(ValueError, match="prefix registration.*sparse-read"):
        InferenceEngine(cfg, p, max_slots=2, max_seq_len=64).register_prefix(
            list(range(40)))
