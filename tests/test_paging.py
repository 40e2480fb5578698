"""Paged KV cache + radix-tree prefix sharing (serve/paging.py).

Correctness oracle, as for the dense engine: greedy rollout through the
full no-cache forward must equal the paged engine's cached decode — with
and without shared prefix pages, across page boundaries, under page
pressure, and mid-divergence of requests sharing pages copy-on-write.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import forward, init_params
from runbooks_tpu.serve.engine import (
    EngineOverloaded,
    InferenceEngine,
    Request,
)
from runbooks_tpu.serve.paging import (
    PageAllocator,
    PagedInferenceEngine,
    RadixTree,
    page_bucket,
    paged_prefill_shapes,
    prefix_page_buckets,
    view_page_buckets_for,
)


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


def greedy_rollout(cfg, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits, _ = forward(cfg, params, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


# ---------------------------------------------------------------------------
# Allocator
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_refcount_invariants():
    a = PageAllocator(4)
    assert (a.free_count, a.used_count) == (4, 0)
    pages = a.alloc(3)
    assert sorted(pages) == pages and len(set(pages)) == 3
    assert (a.free_count, a.used_count) == (1, 3)
    assert all(a.refcount(p) == 1 for p in pages)
    # all-or-nothing: an unsatisfiable request takes nothing
    assert a.alloc(2) is None
    assert a.free_count == 1
    a.incref(pages[:1])
    assert a.refcount(pages[0]) == 2
    # one decref does not free a shared page; the second does
    assert a.decref(pages[:1]) == []
    assert a.decref(pages[:1]) == [pages[0]]
    assert a.free_count == 2
    # freeing the rest returns everything
    a.decref(pages[1:])
    assert (a.free_count, a.used_count) == (4, 0)
    with pytest.raises(RuntimeError):
        a.decref([pages[0]])  # double-free is a bug, not a no-op
    with pytest.raises(RuntimeError):
        a.incref([pages[0]])  # incref of a free page likewise


# ---------------------------------------------------------------------------
# Radix tree
# ---------------------------------------------------------------------------

def test_radix_match_insert_partial_page_boundary():
    a = PageAllocator(8)
    t = RadixTree(4, a)
    toks = list(range(10))          # 2 full pages + a 2-token tail
    pages = a.alloc(3)              # page 2 holds the partial tail
    adopted = t.insert(toks, pages)
    # only COMPLETE pages enter the tree — the partial tail page never
    # becomes shareable (its tail garbage must not be attributed tokens)
    assert adopted == 2 and t.nodes == 2
    assert t.match(toks) == pages[:2]
    # a shorter query matches only whole pages it covers
    assert t.match(toks[:7]) == pages[:1]
    assert t.match(toks[:3]) == []
    # diverging second sequence shares page 0, adds its own page 1
    toks2 = toks[:4] + [99, 98, 97, 96]
    pages2 = a.alloc(2)
    assert t.insert(toks2, pages2) == 1          # page 0 already present
    assert t.match(toks2) == [pages[0], pages2[1]]
    # the duplicate page2[0] stays the caller's: tree never took a ref
    assert a.refcount(pages2[0]) == 1
    assert a.refcount(pages[0]) == 2             # caller + tree


def test_radix_evict_lru_and_refcount_pinning():
    a = PageAllocator(8)
    t = RadixTree(2, a)
    old = a.alloc(2)
    t.insert([1, 2, 3, 4], old)
    new = a.alloc(2)
    t.insert([5, 6, 7, 8], new)
    # callers drop their refs; tree-only pages are evictable
    a.decref(old)
    a.decref(new)
    t.match([5, 6, 7, 8])  # refresh: `new` is most recently used
    assert t.evict(1) == 1
    # LRU victim is the *leaf* of the old chain (depth-first from the
    # tail); its parent remains until a later round
    assert t.match([1, 2, 3, 4]) == old[:1]
    assert a.free_count == 5
    # a pinned page (live slot ref) is never evicted
    a.incref([new[0]])
    freed = t.evict(10)
    assert a.refcount(new[0]) == 2               # still tree + pin
    assert t.match([5, 6]) == [new[0]]
    # everything unpinned is gone (old chain fully cascaded)
    assert t.match([1, 2]) == []
    assert freed == 2                            # old[0] + new[1]
    assert t.pages_evicted == 3


def test_bucket_helpers():
    assert prefix_page_buckets(4) == [1, 2, 4]
    assert prefix_page_buckets(6) == [1, 2, 4, 6]
    assert [page_bucket(n, 4) for n in (0, 1, 2, 3, 4)] == [0, 1, 2, 4, 4]
    assert view_page_buckets_for(64, 16) == [4]
    shapes = paged_prefill_shapes([16, 32, 64], 4, 16, 64)
    # every reachable (suffix bucket, prefix-page bucket): ppb=4 (min 3
    # pages = 48 shared tokens) leaves at most a 16-token suffix
    assert (64, 4) not in shapes and (32, 4) not in shapes
    assert (16, 4) in shapes and (64, 1) in shapes
    assert len(shapes) == 9


# ---------------------------------------------------------------------------
# Engine parity vs the dense oracle
# ---------------------------------------------------------------------------

def test_paged_matches_dense_greedy(model):
    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=4, page_size=16)
    prompts = [[5, 9, 17], list(range(3, 21)), [42]]
    reqs = [Request(prompt_tokens=p, max_tokens=8, temperature=0.0)
            for p in prompts]
    engine.generate(reqs)
    for p, r in zip(prompts, reqs):
        expect = greedy_rollout(cfg, params, p, 8)
        assert r.output_tokens == expect, (p, r.output_tokens, expect)
    # all pages released or adopted: nothing leaked to dead slots
    occ = engine.pager.occupancy()
    assert occ["pages_used"] == occ["pages_shared"]


def test_paged_matches_dense_greedy_bf16():
    cfg = tiny_cfg(dtype="bfloat16")
    params = init_params(cfg, jax.random.key(0))
    dense = InferenceEngine(cfg, params, max_slots=2)
    paged = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16)
    prompt = list(range(7, 27))
    rd = Request(prompt_tokens=prompt, max_tokens=8, temperature=0.0)
    rp = Request(prompt_tokens=prompt, max_tokens=8, temperature=0.0)
    dense.generate([rd])
    paged.generate([rp])
    assert rd.output_tokens == rp.output_tokens


def test_paged_int8_kv_matches_dense_int8(model):
    cfg, params = model
    dense = InferenceEngine(cfg, params, max_slots=2, quantize_kv=True)
    paged = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                                 quantize_kv=True)
    assert paged.cache.quantized
    prompt = [7, 3, 11, 2, 9, 40, 41]
    rd = Request(prompt_tokens=prompt, max_tokens=8, temperature=0.0)
    rp = Request(prompt_tokens=prompt, max_tokens=8, temperature=0.0)
    dense.generate([rd])
    paged.generate([rp])
    # identical quantize-at-write / dequantize-at-read path: exact match
    assert rd.output_tokens == rp.output_tokens


def test_shared_prefix_parity_and_page_accounting(model):
    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=4, page_size=16)
    shared = list(range(1, 34))      # 33 tokens -> 2 full shared pages
    assert engine.register_prefix(shared) == 32
    assert engine.has_prefix(shared + [99])
    occ = engine.pager.occupancy()
    assert occ["pages_shared"] == 2
    r = Request(prompt_tokens=shared + [50, 51], max_tokens=6,
                temperature=0.0)
    engine.generate([r])
    assert r.output_tokens == greedy_rollout(cfg, params,
                                             shared + [50, 51], 6)
    # per-page reuse accounting: 2 physical pages mapped, 32 tokens not
    # re-prefilled, one admission-level hit
    assert engine.pager.pages_reused_total == 2
    assert engine.prefix_tokens_reused == 32
    assert (engine.prefix_hits, engine.prefix_lookups) == (1, 2)


def test_cow_divergence_mid_generation(model):
    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=4, page_size=16)
    shared = list(range(1, 33))      # exactly 2 pages
    engine.register_prefix(shared)
    base = engine.pager.occupancy()["pages_shared"]
    # two CONCURRENT requests share the prefix pages and diverge from
    # the first private token; each must match its own oracle (a write
    # leaking into a shared page would corrupt the sibling)
    ra = Request(prompt_tokens=shared + [50], max_tokens=8,
                 temperature=0.0)
    rb = Request(prompt_tokens=shared + [60, 61], max_tokens=8,
                 temperature=0.0)
    engine.submit(ra)
    engine.submit(rb)
    while engine.has_work():
        engine.step()
    assert ra.output_tokens == greedy_rollout(cfg, params,
                                              shared + [50], 8)
    assert rb.output_tokens == greedy_rollout(cfg, params,
                                              shared + [60, 61], 8)
    assert engine.pager.pages_reused_total >= 4  # 2 pages x 2 requests
    # and the shared pages survived both generations intact: a THIRD
    # request over the same prefix still matches its oracle
    rc = Request(prompt_tokens=shared + [70], max_tokens=6,
                 temperature=0.0)
    engine.generate([rc])
    assert rc.output_tokens == greedy_rollout(cfg, params,
                                              shared + [70], 6)
    assert engine.pager.occupancy()["pages_shared"] >= base


def test_finished_requests_seed_the_radix_tree(model):
    """Many-user prefix reuse without any registration: request 1's
    prompt pages are adopted at finish; request 2 (same system prompt,
    different question) reuses them."""
    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16)
    system = list(range(2, 20))      # 18 tokens -> 1 full page
    r1 = Request(prompt_tokens=system + [30], max_tokens=4,
                 temperature=0.0)
    engine.generate([r1])
    assert engine.pager.occupancy()["pages_shared"] >= 1
    assert engine.pager.pages_reused_total == 0
    r2 = Request(prompt_tokens=system + [31, 32], max_tokens=6,
                 temperature=0.0)
    engine.generate([r2])
    assert engine.pager.pages_reused_total >= 1
    assert r2.output_tokens == greedy_rollout(cfg, params,
                                              system + [31, 32], 6)


def test_multi_turn_adoption_extends_the_match(model):
    """Turn 2's prompt extends turn 1's prompt + reply: the pages written
    during generation (minus the never-written final token) are
    shareable, so the match deepens turn over turn — the paged
    generalization of the dense engine's auto_prefix."""
    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16)
    prompt1 = list(range(1, 30))     # 29 tokens
    r1 = Request(prompt_tokens=prompt1, max_tokens=8, temperature=0.0)
    engine.generate([r1])
    # written extent = 29 + 8 - 1 = 36 -> 2 full pages adopted
    assert engine.pager.occupancy()["pages_shared"] == 2
    prompt2 = prompt1 + r1.output_tokens + [77]
    r2 = Request(prompt_tokens=prompt2, max_tokens=6, temperature=0.0)
    engine.generate([r2])
    assert engine.pager.pages_reused_total == 2
    assert r2.output_tokens == greedy_rollout(cfg, params, prompt2, 6)


def test_register_prefix_from_slot_is_noop_and_safe(model):
    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16)
    assert engine.register_prefix_from_slot(0, [1, 2, 3]) == 0
    assert engine.prefix_warmup_shapes(32) == []
    assert engine.warm_prefix_shape((1,), 16, 1, None) is None


# ---------------------------------------------------------------------------
# Page pressure: backpressure, eviction, no corruption
# ---------------------------------------------------------------------------

def test_page_pressure_serializes_and_stays_correct(model):
    cfg, params = model
    # 4 slots but only enough pages for ONE max-reservation request at a
    # time: admission must serialize on pages, never corrupt
    engine = PagedInferenceEngine(cfg, params, max_slots=4, page_size=16,
                                  num_pages=4)
    prompts = [list(range(1, 33)), list(range(40, 72)),
               list(range(60, 92))]
    reqs = [Request(prompt_tokens=p, max_tokens=32, temperature=0.0)
            for p in prompts]    # reserve = 64 tokens = 4 pages each
    for r in reqs:
        engine.submit(r)
    engine.step()
    assert int(engine.active.sum()) == 1     # pages, not slots, gate
    assert len(engine.queue) == 2
    while engine.has_work():
        engine.step()
    for p, r in zip(prompts, reqs):
        expect = greedy_rollout(cfg, params, p,
                                len(r.output_tokens))
        assert r.output_tokens == expect


def test_page_exhaustion_backpressure_is_typed_overload(model):
    """The 429 path: a full pool backs the queue up; past max_queue,
    submit sheds with the same typed EngineOverloaded the HTTP layer
    maps to 429 + Retry-After — requests are never admitted into a pool
    that cannot hold them."""
    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=4, page_size=16,
                                  num_pages=4, max_queue=2)
    mk = lambda i: Request(prompt_tokens=list(range(i, i + 32)),
                           max_tokens=32, temperature=0.0)
    engine.submit(mk(1))
    engine.step()                     # admitted: pool now full
    engine.submit(mk(2))
    engine.submit(mk(3))              # queue at its bound
    with pytest.raises(EngineOverloaded):
        engine.submit(mk(4))
    while engine.has_work():
        engine.step()


def test_eviction_makes_room_then_recomputes_evicted_prefix(model):
    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                                  num_pages=5)
    shared = list(range(1, 33))
    engine.register_prefix(shared)    # 2 tree pages resident
    assert engine.pager.occupancy()["pages_shared"] == 2
    # a non-matching max-reservation request needs 4 pages -> evicts at
    # least one unreferenced prefix page
    big = Request(prompt_tokens=list(range(90, 122)), max_tokens=32,
                  temperature=0.0)
    engine.generate([big])
    assert engine.pager.radix.pages_evicted >= 1
    # the evicted prefix simply recomputes — correctness is unaffected
    r = Request(prompt_tokens=shared + [50], max_tokens=5,
                temperature=0.0)
    engine.generate([r])
    assert r.output_tokens == greedy_rollout(cfg, params, shared + [50],
                                             5)


def test_deadline_expiry_releases_pages(model):
    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16)
    r = Request(prompt_tokens=list(range(1, 20)), max_tokens=64,
                temperature=0.0, deadline_s=0.0)
    engine.submit(r)
    engine.step()
    # queued request expired before admission: empty-handed, zero pages
    assert r.finish_reason == "deadline"
    occ = engine.pager.occupancy()
    assert occ["pages_used"] == occ["pages_shared"]
    # active request expiring mid-generation frees its private pages too
    r2 = Request(prompt_tokens=list(range(1, 20)), max_tokens=64,
                 temperature=0.0, deadline_s=30.0)
    engine.submit(r2)
    engine.step()
    assert engine.active.any()
    r2.deadline_s = 0.0               # force expiry at the next step
    engine.step()
    assert r2.finish_reason == "deadline"
    occ = engine.pager.occupancy()
    assert occ["pages_used"] == occ["pages_shared"]


def test_geometry_validation(model):
    cfg, params = model
    with pytest.raises(ValueError, match="divide"):
        PagedInferenceEngine(cfg, params, max_slots=2, page_size=24)
    with pytest.raises(ValueError, match="one max-length"):
        PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                             num_pages=2)
    with pytest.raises(ValueError, match="mesh"):
        PagedInferenceEngine(cfg, params, max_slots=2, mesh=object())


# ---------------------------------------------------------------------------
# Compile discipline
# ---------------------------------------------------------------------------

def test_zero_unexpected_compiles_in_paged_steady_loop(model):
    from runbooks_tpu.obs import device as obs_device

    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16)
    try:
        engine.warmup()
        census = engine.warmup_census
        assert census["prefill_programs"] == 9 * 2  # shapes x rows
        assert census["decode_views"] == [4]
        sentinel = obs_device.SENTINEL
        before = sentinel.unexpected
        # steady traffic across every paged code path: plain admission,
        # radix-hit admission (several prefix-page buckets), batched
        # groups, decode, finish-adoption
        shared = list(range(1, 33))
        engine.register_prefix(shared)
        reqs = [Request(prompt_tokens=shared + [40 + i], max_tokens=5,
                        temperature=0.0) for i in range(3)]
        reqs += [Request(prompt_tokens=[9, 8, 7], max_tokens=5,
                         temperature=0.0)]
        for r in reqs:
            engine.submit(r)
        while engine.has_work():
            engine.step()
        assert all(r.finished for r in reqs)
        assert sentinel.unexpected == before, sentinel.recent_unexpected()
    finally:
        engine.release_steady()


# ---------------------------------------------------------------------------
# Serving surface: metrics, /debug/memory, rbt top, controller params
# ---------------------------------------------------------------------------

def test_http_paged_server_metrics_and_memory(model):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg, params = model
    app = create_server(cfg, params, max_slots=2, kv_paging=True,
                        page_size=16, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "hello paging", "max_tokens": 4,
                "temperature": 0.0})
            assert r.status == 200
            r = await client.get("/metrics")
            assert r.status == 200
            text = await r.text()
            for fam in ("serve_kv_pages_free", "serve_kv_pages_used",
                        "serve_kv_pages_shared",
                        "serve_prefix_pages_reused_total"):
                assert f"\n{fam} " in text or text.startswith(
                    f"{fam} "), fam
            r = await client.get("/debug/memory")
            assert r.status == 200
            body = await r.json()
            occ = body["kv_occupancy"]
            assert occ["paged"] and occ["page_size"] == 16
            # page-level byte attribution: shared (prefix_cache-like)
            # vs private bytes inside the one physical pool
            assert occ["kv_bytes_shared"] + occ["kv_bytes_private"] \
                == occ["pages_used"] * occ["bytes_per_page"]

    asyncio.run(drive())


def test_dense_metrics_do_not_export_page_series(model):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.obs import metrics as obs_metrics
    from runbooks_tpu.serve.api import create_server

    cfg, params = model
    # the process-wide registry may carry page series from a paged test
    # in this module — a fresh registry proves the DENSE path never sets
    # them (reset() is the test-only full wipe)
    obs_metrics.REGISTRY.reset()
    app = create_server(cfg, params, max_slots=2, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.get("/metrics")
            return await r.text()

    text = asyncio.run(drive())
    assert "serve_kv_pages_used" not in text
    assert "serve_kv_occupancy_ratio" in text


def test_rbt_top_slots_cell_paged_vs_dense():
    from runbooks_tpu.cli.main import _top_slots
    from runbooks_tpu.obs.metrics import parse_exposition

    paged = parse_exposition(
        "serve_active_slots 3\nserve_slots_total 8\n"
        "serve_kv_occupancy_ratio 0.5\n"
        "serve_kv_pages_free 48\nserve_kv_pages_used 16\n"
        "serve_kv_pages_shared 8\n")
    assert _top_slots(paged, {}) == "3/8 kv=25% shared=12%"
    dense = parse_exposition(
        "serve_active_slots 3\nserve_slots_total 8\n"
        "serve_kv_occupancy_ratio 0.5\n")
    assert _top_slots(dense, {}) == "3/8 kv=50%"


def test_validate_params_kv_paging():
    from runbooks_tpu.controller.common import validate_params

    assert validate_params({"kv_paging": "paged", "page_size": 16,
                            "num_pages": 512}) is None
    assert validate_params({"kvPaging": "off"}) is None
    assert "kv_paging" in validate_params({"kv_paging": "pagedd"})
    assert "page_size" in validate_params({"page_size": 0})
    assert "num_pages" in validate_params({"num_pages": "many"})
