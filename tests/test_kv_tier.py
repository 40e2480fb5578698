"""QoS classes + host-RAM KV swap tier (ISSUE 19).

Covers: the HostPagePool staging tier (bit-identical store/load round
trips, deterministic slot handout, double-free detection), swap-out on
radix eviction and swap-in on a returning session's admission (token-
identical with the no-cache greedy oracle), slot preemption under class
pressure with loss-free resume, class-ordered admission queues and
per-class queue shares, the load-derived Retry-After hint, swapfail
fault injection degrading to drop/recompute without crashing or leaking
either tier, exact refcount balance across both tiers after deadline
expiry of a preempted request, zero unexpected XLA compiles in a steady
loop with live swap + preemption traffic, and the HTTP surface
(priority validation, X-Priority header, swap/preemption metric
families, /debug/memory host census, 429 Retry-After).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import forward, init_params
from runbooks_tpu.serve.engine import (
    PRIORITY_RANK,
    EngineOverloaded,
    InferenceEngine,
    Request,
)
from runbooks_tpu.serve.paging import (
    HostPagePool,
    PagedInferenceEngine,
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
# HostPagePool
# ---------------------------------------------------------------------------

def test_host_pool_alloc_store_load_invariants():
    cfg = tiny_cfg()
    pool = HostPagePool(cfg, host_pages=2, page_size=16)
    assert (pool.free_count, pool.used_count) == (2, 0)
    # ascending deterministic handout; exhaustion returns None, never
    # raises (the caller chooses evict_host vs degrade-to-drop)
    a, b = pool.alloc(), pool.alloc()
    assert (a, b) == (0, 1)
    assert pool.alloc() is None
    page_shape = (cfg.num_layers, 16, cfg.num_kv_heads, cfg.head_dim)
    k = np.random.default_rng(0).standard_normal(page_shape).astype(
        np.float32)
    v = np.random.default_rng(1).standard_normal(page_shape).astype(
        np.float32)
    pool.store(a, k, v)
    lk, lv = pool.load(a)
    # bit-identical round trip: swap-in must reproduce the evicted
    # page's K/V exactly, or resumed decodes drift from the oracle
    assert np.array_equal(lk, k) and np.array_equal(lv, v)
    pool.free(a)
    assert (pool.free_count, pool.used_count) == (1, 1)
    with pytest.raises(RuntimeError):
        pool.free(a)                 # double-free is a bug, not a no-op
    with pytest.raises(RuntimeError):
        pool.load(a)                 # load of a freed slot likewise
    with pytest.raises(RuntimeError):
        pool.store(a, k, v)
    with pytest.raises(ValueError):
        HostPagePool(cfg, host_pages=0, page_size=16)


# ---------------------------------------------------------------------------
# Swap round trip: evict to host, return, swap back in
# ---------------------------------------------------------------------------

def test_swap_roundtrip_matches_oracle(model):
    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                                  num_pages=5, kv_host_pages=4)
    shared = list(range(1, 33))
    engine.register_prefix(shared)    # 2 tree pages resident in HBM
    assert engine.pager.occupancy()["pages_shared"] == 2
    # a non-matching max-reservation request forces eviction; with the
    # host tier wired, evicted prefix pages COPY to host instead of
    # dropping
    big = Request(prompt_tokens=list(range(90, 122)), max_tokens=32,
                  temperature=0.0)
    engine.generate([big])
    occ = engine.pager.occupancy()
    assert occ["swap_out_pages_total"] >= 1
    assert occ["host_pages_used"] >= 1
    # the returning session swaps its prefix back into HBM — admission
    # rides the normal radix-match path, paying a device_put instead of
    # recomputing the prefill — and the tokens are identical to the
    # no-cache oracle
    r = Request(prompt_tokens=shared + [50], max_tokens=5,
                temperature=0.0)
    engine.generate([r])
    assert r.output_tokens == greedy_rollout(cfg, params, shared + [50],
                                             5)
    occ = engine.pager.occupancy()
    assert occ["swap_in_pages_total"] >= 1


# ---------------------------------------------------------------------------
# Preemption: displace batch for interactive, resume with no token loss
# ---------------------------------------------------------------------------

def test_preemption_resumes_without_token_loss(model):
    cfg, params = model
    # decode_chunk=2 keeps the batch request mid-flight for several
    # steps regardless of the platform tuning table
    engine = PagedInferenceEngine(cfg, params, max_slots=1, page_size=16,
                                  num_pages=5, kv_host_pages=8,
                                  preemption="swap", decode_chunk=2)
    batch = Request(prompt_tokens=list(range(1, 33)), max_tokens=16,
                    temperature=0.0, priority="batch")
    engine.submit(batch)
    for _ in range(3):                # admit + decode a few tokens
        engine.step()
    assert engine.active.any() and not batch.finished
    inter = Request(prompt_tokens=list(range(90, 106)), max_tokens=8,
                    temperature=0.0, priority="interactive")
    engine.submit(inter)
    engine.step()
    # the only slot held a strictly-worse class while interactive waited
    # on capacity: the batch request was displaced at the step boundary
    assert engine.preemptions == 1
    assert not batch.finished         # re-queued, not shed
    while engine.has_work():
        engine.step()
    assert engine.preempted_resumed == 1
    # loss-free resume: the preempted request's final output is token-
    # identical to an undisturbed greedy run, finish_reason unchanged
    assert batch.output_tokens == greedy_rollout(
        cfg, params, batch.prompt_tokens, 16)
    assert batch.finish_reason == "length"
    assert inter.output_tokens == greedy_rollout(
        cfg, params, inter.prompt_tokens, 8)


# ---------------------------------------------------------------------------
# QoS admission: class-ordered queue, per-class shares, Retry-After
# ---------------------------------------------------------------------------

def test_queue_class_ordering_and_shares(model):
    cfg, params = model
    engine = InferenceEngine(cfg, params, max_slots=1, max_queue=10,
                             queue_share_batch=0.2)
    # batch's share bounds it to ceil(0.2 * 10) = 2 queued entries —
    # the third sheds while other classes keep their queue room
    mk = lambda pri, t: Request(prompt_tokens=[t, t + 1], max_tokens=2,
                                temperature=0.0, priority=pri)
    engine.submit(mk("batch", 1))
    engine.submit(mk("batch", 3))
    with pytest.raises(EngineOverloaded, match="batch queue share"):
        engine.submit(mk("batch", 5))
    engine.submit(mk("standard", 7))
    engine.submit(mk("interactive", 9))
    # class-ordered queue: interactive ahead of standard ahead of batch,
    # FIFO within a class
    assert [q.priority for q in engine.queue] == \
        ["interactive", "standard", "batch", "batch"]
    assert [q.prompt_tokens[0] for q in engine.queue[2:]] == [1, 3]
    # load-derived Retry-After: queue depth in slot-drain units,
    # clamped to [1, 30]
    assert engine.retry_after_hint() == 4
    for t in range(6):
        engine.submit(mk("standard", 20 + 2 * t))
    assert engine.retry_after_hint() == 10
    engine.queue.extend(engine.queue[:1] * 90)   # synthetic deep backlog
    assert engine.retry_after_hint() == 30
    engine.queue.clear()
    assert engine.retry_after_hint() == 1


def test_qos_validation_is_typed():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    with pytest.raises(TypeError, match="queue_share_urgent"):
        InferenceEngine(cfg, params, max_slots=1, queue_share_urgent=0.5)
    with pytest.raises(ValueError, match="in \\(0, 1\\]"):
        InferenceEngine(cfg, params, max_slots=1,
                        queue_share_batch=0.0)
    # the dense engine has no pages to swap: preemption=swap is a typed
    # construction error pointing at kv_paging, not a silent no-op
    with pytest.raises(ValueError, match="kv_paging: paged"):
        InferenceEngine(cfg, params, max_slots=1, preemption="swap")
    with pytest.raises(ValueError, match="preemption"):
        InferenceEngine(cfg, params, max_slots=1, preemption="maybe")
    engine = InferenceEngine(cfg, params, max_slots=1)
    with pytest.raises(ValueError, match="priority"):
        engine.validate(Request(prompt_tokens=[1, 2], max_tokens=2,
                                priority="urgent"))


# ---------------------------------------------------------------------------
# Fault injection: swap copies fail, the engine degrades, nothing leaks
# ---------------------------------------------------------------------------

def test_swapfail_degrades_swap_out_to_drop(model, monkeypatch):
    cfg, params = model
    monkeypatch.setenv("RBT_FAULT_INJECT", "swapfail:1")
    engine = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                                  num_pages=5, kv_host_pages=4)
    shared = list(range(1, 33))
    engine.register_prefix(shared)
    big = Request(prompt_tokens=list(range(90, 122)), max_tokens=32,
                  temperature=0.0)
    engine.generate([big])            # first swap copy fails -> drop
    occ = engine.pager.occupancy()
    assert occ["swap_dropped_pages_total"] >= 1
    # the dropped prefix recomputes; correctness is unaffected
    r = Request(prompt_tokens=shared + [50], max_tokens=5,
                temperature=0.0)
    engine.generate([r])
    assert r.output_tokens == greedy_rollout(cfg, params, shared + [50],
                                             5)


def test_swapfail_degrades_swap_in_to_recompute(model):
    cfg, params = model
    # a roomy pool: the returning admission below must need NO eviction,
    # so the armed fault lands on its swap-in, not an eviction's swap-out
    engine = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                                  num_pages=8, kv_host_pages=4)
    shared = list(range(1, 33))
    engine.register_prefix(shared)
    # push the idle prefix to the host tier (healthy swap-outs)
    assert engine.pager.radix.evict(2) == 2
    assert engine.pager.occupancy()["host_pages_used"] == 2
    # arm the injector: the next copy attempt is the returning session's
    # swap-in, which must roll back the admission (failed node dropped
    # from the tree) and recompute — degrade, never crash or leak
    engine._swap_fault = 1
    r = Request(prompt_tokens=shared + [50], max_tokens=5,
                temperature=0.0)
    engine.generate([r])
    assert r.output_tokens == greedy_rollout(cfg, params, shared + [50],
                                             5)
    assert engine.pager.occupancy()["swap_in_pages_total"] == 0
    # both tiers drain to exactly zero: every reference taken during the
    # rolled-back admission was returned
    engine.pager.radix.evict(10 ** 6)
    engine.pager.radix.evict_host(10 ** 6)
    assert engine.pager.allocator.used_count == 0
    assert engine.host_pool.used_count == 0


def test_swapfail_spec_is_validated(monkeypatch):
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    monkeypatch.setenv("RBT_FAULT_INJECT", "swapfail:0")
    with pytest.raises(ValueError, match="K must be >= 1"):
        InferenceEngine(cfg, params, max_slots=1)
    monkeypatch.setenv("RBT_FAULT_INJECT", "swapfail:soon")
    with pytest.raises(ValueError, match="swapfail:K"):
        InferenceEngine(cfg, params, max_slots=1)


# ---------------------------------------------------------------------------
# Release guarantees: deadline expiry of a preempted request
# ---------------------------------------------------------------------------

def test_preempted_deadline_expiry_balances_both_tiers(model):
    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=1, page_size=16,
                                  num_pages=5, kv_host_pages=4,
                                  preemption="swap", decode_chunk=2)
    batch = Request(prompt_tokens=list(range(1, 33)), max_tokens=16,
                    temperature=0.0, priority="batch", deadline_s=30.0)
    engine.submit(batch)
    for _ in range(3):
        engine.step()
    inter = Request(prompt_tokens=list(range(90, 106)), max_tokens=8,
                    temperature=0.0, priority="interactive")
    engine.submit(inter)
    engine.step()
    assert engine.preemptions == 1 and not batch.finished
    # the preempted request's deadline expires while it waits in the
    # queue (a disconnecting client rides the same expiry path): it
    # finishes empty-handed and its adopted pages stay shareable tree
    # state, owned by the hierarchy — not leaked to a dead request
    batch.deadline_s = 0.0
    engine.step()
    assert batch.finish_reason == "deadline"
    while engine.has_work():
        engine.step()
    assert inter.finish_reason == "length"
    occ = engine.pager.occupancy()
    assert occ["pages_used"] == occ["pages_shared"]
    # evict everything from both tiers: the refcounts balance exactly —
    # zero pages held on either tier once the trees are emptied
    engine.pager.radix.evict(10 ** 6)
    engine.pager.radix.evict_host(10 ** 6)
    assert engine.pager.allocator.used_count == 0
    assert engine.host_pool.used_count == 0


# ---------------------------------------------------------------------------
# Compile discipline with live swap + preemption traffic
# ---------------------------------------------------------------------------

def test_zero_unexpected_compiles_with_swap_and_preemption(model):
    from runbooks_tpu.obs import device as obs_device

    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                                  num_pages=5, kv_host_pages=8,
                                  preemption="swap", decode_chunk=2)
    try:
        engine.warmup()
        census = engine.warmup_census
        # one warmed program per swap direction, page index traced
        assert census["swap_programs"] == 2
        assert census["kv_host_pages"] == 8
        sentinel = obs_device.SENTINEL
        before = sentinel.unexpected
        # steady traffic across every tier transition: eviction-driven
        # swap-out, returning-session swap-in, preemption adoption, and
        # preempted-resume
        shared = list(range(1, 33))
        engine.register_prefix(shared)
        big = Request(prompt_tokens=list(range(90, 122)), max_tokens=32,
                      temperature=0.0)
        engine.generate([big])
        back = Request(prompt_tokens=shared + [50], max_tokens=5,
                       temperature=0.0)
        engine.generate([back])
        batches = [Request(prompt_tokens=list(range(40 + 8 * i,
                                                    56 + 8 * i)),
                           max_tokens=16, temperature=0.0,
                           priority="batch") for i in range(2)]
        for b in batches:
            engine.submit(b)
        for _ in range(3):
            engine.step()
        inter = Request(prompt_tokens=list(range(70, 86)), max_tokens=8,
                        temperature=0.0, priority="interactive")
        engine.submit(inter)
        while engine.has_work():
            engine.step()
        assert all(r.finished for r in batches + [inter, big, back])
        occ = engine.pager.occupancy()
        assert occ["swap_out_pages_total"] >= 1
        assert occ["swap_in_pages_total"] >= 1
        assert engine.preemptions >= 1
        assert engine.preemptions == engine.preempted_resumed
        assert sentinel.unexpected == before, sentinel.recent_unexpected()
    finally:
        engine.release_steady()


# ---------------------------------------------------------------------------
# HTTP surface: priority plumbing, metric families, host census
# ---------------------------------------------------------------------------

def test_http_qos_and_host_tier_surface(model):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg, params = model
    app = create_server(cfg, params, max_slots=2, kv_paging=True,
                        page_size=16, num_pages=5, kv_host_pages=2,
                        preemption="swap",
                        queue_share_batch=0.5, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 2, "temperature": 0.0,
                "priority": "urgent"})
            assert r.status == 400
            body = await r.json()
            assert "priority" in body["error"]["message"]
            # body field beats the X-Priority header; either spelling of
            # a valid class is accepted case-insensitively
            r = await client.post(
                "/v1/completions",
                json={"prompt": "hello", "max_tokens": 2,
                      "temperature": 0.0, "priority": "Batch"},
                headers={"X-Priority": "interactive"})
            assert r.status == 200
            r = await client.post(
                "/v1/completions",
                json={"prompt": "hello again", "max_tokens": 2,
                      "temperature": 0.0},
                headers={"X-Priority": "interactive"})
            assert r.status == 200
            r = await client.get("/metrics")
            text = await r.text()
            for fam in ("serve_kv_host_pages_used",
                        "serve_kv_host_pages_free",
                        "serve_kv_swap_out_pages_total",
                        "serve_kv_swap_in_pages_total",
                        "serve_kv_swap_dropped_pages_total",
                        "serve_preemptions_total",
                        "serve_preempted_resumed_total"):
                assert f"\n{fam} " in text or text.startswith(
                    f"{fam} "), fam
            r = await client.get("/debug/memory")
            occ = (await r.json())["kv_occupancy"]
            assert occ["host_pages_total"] == 2
            assert occ["host_pages_used"] + occ["host_pages_free"] == 2

    asyncio.run(drive())


def test_http_shed_carries_load_derived_retry_after(model):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg, params = model
    app = create_server(cfg, params, max_slots=1, max_queue=0, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "shed me", "max_tokens": 2})
            assert r.status == 429
            # load-derived hint, not a hardcoded constant: an empty
            # queue drains in one slot turn
            assert r.headers.get("Retry-After") == "1"

    asyncio.run(drive())


# ---------------------------------------------------------------------------
# Controller validation
# ---------------------------------------------------------------------------

def test_validate_params_kv_tier():
    from runbooks_tpu.controller.common import validate_params

    assert validate_params({"kv_paging": "paged", "kv_host_pages": 64,
                            "preemption": "swap",
                            "queue_share_batch": 0.25}) is None
    assert validate_params({"kvPaging": "paged",
                            "kvHostPages": 8}) is None
    # typed errors, never a silent default
    assert "preemption" in validate_params({"kv_paging": "paged",
                                            "preemption": "swa"})
    assert "kv_host_pages" in validate_params({"kv_paging": "paged",
                                               "kv_host_pages": -1})
    assert "kv_host_pages" in validate_params({"kv_paging": "paged",
                                               "kv_host_pages": "many"})
    assert "queue_share_batch" in validate_params(
        {"queue_share_batch": 0})
    assert "queueShareInteractive" in validate_params(
        {"queueShareInteractive": 1.5})
    # cross-field: both features swap radix PAGES — they need the paged
    # engine, and the error says so
    err = validate_params({"kv_host_pages": 4})
    assert "kv_paging: paged" in err
    err = validate_params({"preemption": "swap"})
    assert "kv_paging: paged" in err
    assert PRIORITY_RANK == {"interactive": 0, "standard": 1, "batch": 2}
