"""Multi-tenant batched LoRA serving tests (docs/multi-tenant-lora.md).

Parity discipline:

- **float32**: the pooled engine's runtime delta ``x@W + (x@A)@B`` and
  the merged oracle's ``x@(W + s·AB)`` agree to f32 rounding, so a
  heterogeneous-adapter batch is token-for-token identical to dedicated
  per-adapter MERGED-weights engines (the load-time fold path — the
  acceptance oracle).
- **bf16 / int8-quantized base**: folding rounds ``W + ΔW`` at weight
  precision while the runtime path keeps W exact and adds a bf16 delta —
  mathematically equal, numerically ~2^-8 apart, so greedy argmax on a
  random tiny model diverges mid-rollout. At serving precision the
  invariant that must hold exactly is BATCHING NEUTRALITY: a tenant's
  output in a heterogeneous multi-tenant batch is token-for-token what a
  single-tenant engine (same precision, same delta arithmetic) produces,
  dense AND paged (the same engine-vs-engine discipline the paged-KV
  parity tests use). The merged oracle still pins the prefill argmax
  (first token), which survives the rounding gap on these seeds.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.api.serve_params import ServeOptions
from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import forward, init_params
from runbooks_tpu.ops.quantization import quantize_params
from runbooks_tpu.serve.engine import (
    EngineOverloaded,
    InferenceEngine,
    Request,
)
from runbooks_tpu.serve.lora_pool import (
    AdapterLoadError,
    AdapterPool,
    load_adapter_tree,
    save_adapter,
)
from runbooks_tpu.serve.paging import PagedInferenceEngine
from runbooks_tpu.train.lora import LoraConfig, apply_lora, init_lora


def tiny_cfg(dtype="float32", **over):
    return dataclasses.replace(
        get_config("llama2-7b"), vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64, dtype=dtype, param_dtype="float32",
        **over)


N_ADAPTERS = 4
# The pool every engine here serves from unless a test sizes its own
# (lora_rank and lora_targets at ServeOptions' defaults).
POOL = 4
TARGETS, RANK = ServeOptions().lora_targets, ServeOptions().lora_rank
PROMPTS = [[5, 9, 17], [3, 4, 5, 6, 7], [40, 2], [8, 8, 8, 9]]


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """Base params + four distinct rank-4 adapters saved as artifacts,
    plus their merged-weights parameter trees."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    root = tmp_path_factory.mktemp("adapters")
    paths, merged, loras = [], [], []
    for i in range(N_ADAPTERS):
        lcfg = LoraConfig(rank=4, alpha=8.0)
        lora = init_lora(params, lcfg, jax.random.key(10 + i))
        # B inits to zero (delta = 0); perturb so each adapter actually
        # changes the model, distinctly per tenant.
        lora = jax.tree.map(
            lambda x, i=i: x + 0.03 * jax.random.normal(
                jax.random.key(20 + i), x.shape, x.dtype), lora)
        path = os.path.join(str(root), f"tenant{i}")
        save_adapter(path, lora, rank=4, alpha=8.0)
        paths.append(path)
        loras.append((lora, lcfg))
        merged.append(apply_lora(params, lora, lcfg))
    return cfg, params, paths, merged, loras


def _reqs(paths, max_tokens=8):
    return [Request(prompt_tokens=list(p), max_tokens=max_tokens,
                    temperature=0.0, adapter=a)
            for p, a in zip(PROMPTS, paths)]


# ---------------------------------------------------------------------------
# Heterogeneous-batch parity vs the merged-weights oracle (float32 exact)
# ---------------------------------------------------------------------------

def test_heterogeneous_batch_parity_dense(world):
    """Four distinct adapters concurrently on ONE dense engine ==
    token-for-token four dedicated merged-weights engines."""
    cfg, params, paths, merged, _ = world
    pooled = InferenceEngine(cfg, params, adapter_pool=POOL,
                             max_slots=N_ADAPTERS)
    reqs = _reqs(paths)
    for r in reqs:
        pooled.submit(r)
    pooled.step()
    # One admission tick filled every slot: heterogeneous tenants rode
    # the same batched dispatch, not one dispatch per tenant.
    assert int(pooled.active.sum()) == N_ADAPTERS
    while pooled.has_work():
        pooled.step()
    for prompt, m, r in zip(PROMPTS, merged, reqs):
        dedicated = InferenceEngine(cfg, m, adapter_pool=POOL,
                                    max_slots=N_ADAPTERS)
        oracle = Request(prompt_tokens=list(prompt), max_tokens=8,
                         temperature=0.0)
        dedicated.generate([oracle])
        assert r.output_tokens == oracle.output_tokens, r.adapter
    stats = pooled.adapter_stats()
    assert stats["loads"] == N_ADAPTERS
    assert sorted(stats["resident"]) == sorted(paths)


def test_heterogeneous_batch_parity_paged(world):
    cfg, params, paths, merged, _ = world
    pooled = PagedInferenceEngine(cfg, params, adapter_pool=POOL,
                                  max_slots=N_ADAPTERS,
                                  page_size=8)
    reqs = _reqs(paths)
    pooled.generate(reqs)
    for prompt, m, r in zip(PROMPTS, merged, reqs):
        dedicated = InferenceEngine(cfg, m, adapter_pool=POOL,
                                    max_slots=N_ADAPTERS)
        oracle = Request(prompt_tokens=list(prompt), max_tokens=8,
                         temperature=0.0)
        dedicated.generate([oracle])
        assert r.output_tokens == oracle.output_tokens, r.adapter


def test_mixed_base_and_adapter_traffic_one_dispatch(world):
    """Base-only rows (trash lane) and tenant rows share one batch; the
    base rows are BITWISE the no-pool engine's output."""
    cfg, params, paths, merged, _ = world
    pooled = InferenceEngine(cfg, params, adapter_pool=POOL, max_slots=3)
    reqs = [
        Request(prompt_tokens=[5, 9, 17], max_tokens=8, temperature=0.0,
                adapter=paths[0]),
        Request(prompt_tokens=[3, 4, 5, 6], max_tokens=8,
                temperature=0.0),
        Request(prompt_tokens=[42, 11], max_tokens=8, temperature=0.0,
                adapter=paths[1]),
    ]
    pooled.generate(reqs)
    plain = InferenceEngine(cfg, params, adapter_pool=0, max_slots=3)
    base_oracle = Request(prompt_tokens=[3, 4, 5, 6], max_tokens=8,
                          temperature=0.0)
    plain.generate([base_oracle])
    assert reqs[1].output_tokens == base_oracle.output_tokens
    for i, m in ((0, merged[0]), (2, merged[1])):
        dedicated = InferenceEngine(cfg, m, adapter_pool=POOL, max_slots=3)
        oracle = Request(prompt_tokens=list(reqs[i].prompt_tokens),
                         max_tokens=8, temperature=0.0)
        dedicated.generate([oracle])
        assert reqs[i].output_tokens == oracle.output_tokens
        # Adapters actually changed the model (deltas not silently zero).
        assert reqs[i].output_tokens != base_oracle.output_tokens or \
            reqs[i].prompt_tokens != base_oracle.prompt_tokens


# ---------------------------------------------------------------------------
# Serving-precision axes: bf16 and int8-quantized base
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantize", ["none", "int8"])
@pytest.mark.parametrize("engine_cls", ["dense", "paged"])
def test_batching_neutrality_bf16_and_int8(world, quantize, engine_cls):
    """bf16 / int8-base: each tenant's output in the heterogeneous batch
    == a single-tenant engine at the same precision, and the prefill
    argmax (first token) == the merged-weights oracle."""
    _, params, paths, _, loras = world
    cfg = tiny_cfg("bfloat16", quantize=quantize)
    # quantize_params packs IN PLACE (deliberate — bounds the load-time
    # f32 footprint); copy the tree structure so the module-scoped
    # fixture's params stay float for the tests after this one.
    eng_params = (quantize_params(jax.tree.map(lambda x: x, params),
                                  quantize)
                  if quantize != "none" else params)

    def make(pool):
        if engine_cls == "paged":
            return PagedInferenceEngine(cfg, eng_params, adapter_pool=pool,
                                        max_slots=N_ADAPTERS, page_size=8)
        return InferenceEngine(cfg, eng_params, adapter_pool=pool,
                               max_slots=N_ADAPTERS)

    multi = make(N_ADAPTERS)
    reqs = _reqs(paths)
    multi.generate(reqs)
    for prompt, path, (lora, lcfg), r in zip(PROMPTS, paths, loras, reqs):
        solo = make(1)
        oracle = Request(prompt_tokens=list(prompt), max_tokens=8,
                         temperature=0.0, adapter=path)
        solo.generate([oracle])
        assert r.output_tokens == oracle.output_tokens, path
        if quantize == "none":
            # Merged-oracle prefill argmax (weight-fold rounding is far
            # smaller than the first token's logit gap on these seeds).
            m = apply_lora(params, lora, lcfg)
            logits, _ = forward(cfg, m, jnp.asarray([prompt], jnp.int32))
            assert r.output_tokens[0] == int(jnp.argmax(logits[0, -1]))


# ---------------------------------------------------------------------------
# Pool residency: eviction, page-back-in, refcount pinning
# ---------------------------------------------------------------------------

def test_pool_eviction_and_page_back_in(world):
    """pool=2 serving 3 tenants round-robin: LRU eviction under
    pressure, page-back-in on return, correctness after reload."""
    cfg, params, paths, merged, _ = world
    eng = InferenceEngine(cfg, params, adapter_pool=2, max_slots=2)
    expected = []
    for prompt, m in zip(PROMPTS[:3], merged[:3]):
        dedicated = InferenceEngine(cfg, m, adapter_pool=POOL, max_slots=2)
        oracle = Request(prompt_tokens=list(prompt), max_tokens=6,
                         temperature=0.0)
        dedicated.generate([oracle])
        expected.append(oracle.output_tokens)
    # Two full rounds over 3 tenants in a 2-lane pool.
    for _round in range(2):
        for i in range(3):
            r = Request(prompt_tokens=list(PROMPTS[i]), max_tokens=6,
                        temperature=0.0, adapter=paths[i])
            eng.generate([r])
            assert r.output_tokens == expected[i], (
                _round, i, eng.adapter_stats())
    stats = eng.adapter_stats()
    assert stats["evictions"] >= 3          # 3 tenants churned 2 lanes
    assert stats["loads"] >= 5              # reloads after eviction
    assert len(stats["resident"]) == 2


def test_pool_refcount_pins_active_lane(world):
    """An adapter pinned by an in-flight request is never the eviction
    victim; releasing it at finish frees the lane."""
    cfg, params, paths, _, _ = world
    pool = AdapterPool(cfg, 2, RANK, TARGETS)
    lane_a = pool.acquire(paths[0])
    lane_b = pool.acquire(paths[1])
    assert {lane_a, lane_b} == {0, 1}
    # Both pinned: a third adapter cannot enter.
    assert pool.acquire(paths[2]) is None
    pool.release(lane_a)
    lane_c = pool.acquire(paths[2])
    assert lane_c == lane_a                 # LRU victim was the freed lane
    assert pool.evictions == 1
    stats = pool.stats()
    assert paths[0] not in stats["resident"]
    assert paths[1] in stats["resident"] and paths[2] in stats["resident"]


def test_admission_429_on_pool_exhaustion(world):
    """Every lane pinned by in-flight decodes: new tenants queue, the
    queue backs up, submit() sheds with the typed 429 — and the queued
    tenant is served once a lane frees."""
    cfg, params, paths, merged, _ = world
    eng = InferenceEngine(cfg, params, adapter_pool=1,
                          max_slots=2, max_queue=2)
    long_req = Request(prompt_tokens=[5, 9, 17], max_tokens=30,
                       temperature=0.0, adapter=paths[0])
    eng.submit(long_req)
    eng.step()                              # adapter 0 pinned by slot
    assert eng.active.any()
    waiting = Request(prompt_tokens=[40, 2], max_tokens=4,
                      temperature=0.0, adapter=paths[1])
    eng.submit(waiting)
    eng.step()
    assert not waiting.finished and waiting in eng.queue  # lane pinned
    eng.submit(Request(prompt_tokens=[1, 2], max_tokens=4,
                       temperature=0.0, adapter=paths[1]))
    with pytest.raises(EngineOverloaded):
        eng.submit(Request(prompt_tokens=[1, 2], max_tokens=4,
                           temperature=0.0, adapter=paths[1]))
    while eng.has_work():
        eng.step()
    assert long_req.finished and waiting.finished
    dedicated = InferenceEngine(cfg, merged[1], adapter_pool=POOL, max_slots=2)
    oracle = Request(prompt_tokens=[40, 2], max_tokens=4, temperature=0.0)
    dedicated.generate([oracle])
    assert waiting.output_tokens == oracle.output_tokens


# ---------------------------------------------------------------------------
# Compile discipline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine_cls", ["dense", "paged"])
def test_zero_unexpected_compiles_steady_adapter_swapping(world,
                                                          engine_cls):
    """Warmed pooled engine: a steady loop that swaps adapters (loads,
    evictions, lane churn, mixed base traffic) performs ZERO XLA
    compiles — pool geometry is static and lane indices are operands."""
    from runbooks_tpu.obs import device as obs_device

    cfg, params, paths, _, _ = world
    if engine_cls == "paged":
        eng = PagedInferenceEngine(cfg, params, adapter_pool=2,
                                   max_slots=2, page_size=8)
    else:
        eng = InferenceEngine(cfg, params, adapter_pool=2, max_slots=2)
    sentinel = obs_device.SENTINEL
    if not sentinel.install():
        pytest.skip("jax.monitoring unavailable; sentinel cannot verify")
    eng.warmup()
    before_unexpected = sentinel.unexpected
    before_total = sentinel.total
    try:
        for i in range(6):
            r = Request(prompt_tokens=list(PROMPTS[i % 4]), max_tokens=4,
                        temperature=0.0,
                        adapter=paths[i % 3] if i % 4 else None)
            eng.generate([r])
            assert r.finished and r.finish_reason != "error"
        stats = eng.adapter_stats()
        assert stats["evictions"] >= 1      # the loop really churned
        assert sentinel.total == before_total, "compiled under traffic"
        assert sentinel.unexpected == before_unexpected
    finally:
        eng.release_steady()


# ---------------------------------------------------------------------------
# Validation + artifact loading
# ---------------------------------------------------------------------------

def test_adapter_request_without_pool_rejected(world):
    cfg, params, paths, _, _ = world
    eng = InferenceEngine(cfg, params, adapter_pool=0, max_slots=2)
    with pytest.raises(ValueError, match="no adapter pool"):
        eng.submit(Request(prompt_tokens=[1, 2], adapter=paths[0]))


def test_unknown_adapter_path_rejected_at_submit(world):
    cfg, params, _, _, _ = world
    eng = InferenceEngine(cfg, params, adapter_pool=POOL, max_slots=2)
    with pytest.raises(ValueError, match="no such directory"):
        eng.submit(Request(prompt_tokens=[1, 2],
                           adapter="/does/not/exist"))


def test_rank_above_bucket_rejected(world, tmp_path):
    """rank > pool bucket cannot pad — load refuses with a clear error
    (lane shapes are static program shapes)."""
    cfg, params, _, _, _ = world
    lcfg = LoraConfig(rank=16, alpha=16.0)
    lora = init_lora(params, lcfg, jax.random.key(7))
    path = str(tmp_path / "bigrank")
    save_adapter(path, lora, rank=16, alpha=16.0)
    with pytest.raises(AdapterLoadError, match="rank 16 exceeds"):
        load_adapter_tree(path, cfg, TARGETS, RANK)


def test_malformed_artifact_raises_typed_error(world, tmp_path):
    """A structurally broken artifact (target values that are not
    {a, b} trees) raises AdapterLoadError — never a raw KeyError that
    would escape the engine's per-request handling into the worker's
    crash-and-reset path."""
    cfg, params, _, _, _ = world
    from runbooks_tpu.train.checkpoint import CheckpointManager

    path = str(tmp_path / "broken")
    mgr = CheckpointManager(path)
    try:
        mgr.save(0, {"params": {
            "attn.wq": np.zeros((2, 64, 4), np.float32)}}, force=True)
        mgr.wait()
    finally:
        mgr.close()
    with pytest.raises(AdapterLoadError, match="not an .a, b. LoRA"):
        load_adapter_tree(path, cfg, TARGETS, RANK)
    # And end to end: the engine finishes the request with an error
    # instead of crashing the loop (load fails only at admission — the
    # artifact dir itself looks valid to the cheap submit-time probe).
    eng = InferenceEngine(cfg, params, adapter_pool=POOL, max_slots=2)
    r = Request(prompt_tokens=[1, 2, 3], max_tokens=4, temperature=0.0,
                adapter=path)
    eng.generate([r])
    assert r.finished and r.finish_reason == "error"
    ok = Request(prompt_tokens=[1, 2, 3], max_tokens=4, temperature=0.0)
    eng.generate([ok])          # the engine still serves
    assert ok.finish_reason == "length"


def test_small_rank_pads_exactly(world, tmp_path):
    """A rank-2 adapter in a rank-8 pool serves exactly its own merged
    oracle (zero-padding contributes nothing)."""
    cfg, params, _, _, _ = world
    lcfg = LoraConfig(rank=2, alpha=4.0)
    lora = init_lora(params, lcfg, jax.random.key(8))
    lora = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.key(9),
                                               x.shape, x.dtype), lora)
    path = str(tmp_path / "r2")
    save_adapter(path, lora, rank=2, alpha=4.0)
    eng = InferenceEngine(cfg, params, adapter_pool=POOL, max_slots=2)
    r = Request(prompt_tokens=[5, 9, 17], max_tokens=6, temperature=0.0,
                adapter=path)
    eng.generate([r])
    dedicated = InferenceEngine(cfg, apply_lora(params, lora, lcfg),
                                adapter_pool=POOL, max_slots=2)
    oracle = Request(prompt_tokens=[5, 9, 17], max_tokens=6,
                     temperature=0.0)
    dedicated.generate([oracle])
    assert r.output_tokens == oracle.output_tokens


def test_load_model_folds_adapter_when_pool_off(world, tmp_path,
                                                monkeypatch):
    """Baseline single-adapter path: `adapter: <path>` with the pool off
    folds at load (serve/api.load_model) — the parity oracle."""
    from runbooks_tpu.serve.api import load_model

    monkeypatch.setenv("RBT_CONTENT_DIR", str(tmp_path / "content"))
    cfg = get_config("debug")
    base = jax.jit(lambda r: init_params(cfg, r))(jax.random.key(0))
    lcfg = LoraConfig(rank=4, alpha=8.0)
    lora = init_lora(base, lcfg, jax.random.key(3))
    lora = jax.tree.map(
        lambda x: x + 0.02 * jax.random.normal(jax.random.key(4),
                                               x.shape, x.dtype), lora)
    path = str(tmp_path / "fold-adapter")
    save_adapter(path, lora, rank=4, alpha=8.0)
    got_cfg, got_params = load_model({"model": "debug", "seed": 0,
                                      "adapter": path})
    want = apply_lora(base, lora, lcfg)
    for a, b in zip(jax.tree.leaves(got_params), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-5, atol=2e-5)
    assert got_cfg.name == "debug"


def test_paged_radix_respects_adapter_namespaces(world):
    """Same prompt prefix, different adapters: pages never cross tenants
    (the K/V differ per adapter); same adapter reuses pages."""
    cfg, params, paths, merged, _ = world
    eng = PagedInferenceEngine(cfg, params, adapter_pool=POOL,
                               max_slots=2, page_size=8)
    long_prompt = list(range(1, 25))
    r1 = Request(prompt_tokens=long_prompt + [30], max_tokens=3,
                 temperature=0.0, adapter=paths[0])
    eng.generate([r1])
    before = eng.pager.pages_reused_total
    r2 = Request(prompt_tokens=long_prompt + [31], max_tokens=3,
                 temperature=0.0, adapter=paths[0])
    eng.generate([r2])
    assert eng.pager.pages_reused_total > before  # same-tenant reuse
    before = eng.pager.pages_reused_total
    r3 = Request(prompt_tokens=long_prompt + [31], max_tokens=3,
                 temperature=0.0, adapter=paths[1])
    eng.generate([r3])
    assert eng.pager.pages_reused_total == before  # tenant isolation
    dedicated = InferenceEngine(cfg, merged[1], adapter_pool=POOL, max_slots=2)
    oracle = Request(prompt_tokens=long_prompt + [31], max_tokens=3,
                     temperature=0.0)
    dedicated.generate([oracle])
    assert r3.output_tokens == oracle.output_tokens


def test_sharded_adapter_engine_matches_unsharded(world):
    """Tensor-sharded serving mesh + adapter pool: the pool device_puts
    by its logical axes and the grouped delta runs SPMD — outputs match
    the meshless engine token for token."""
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg, params, paths, _, _ = world
    plain = InferenceEngine(cfg, params, adapter_pool=POOL, max_slots=2)
    r0 = Request(prompt_tokens=[5, 9, 17], max_tokens=6, temperature=0.0,
                 adapter=paths[0])
    plain.generate([r0])
    sharded = InferenceEngine(cfg, params, adapter_pool=POOL, max_slots=2,
                              mesh=make_mesh(MeshConfig(tensor=2)))
    r1 = Request(prompt_tokens=[5, 9, 17], max_tokens=6, temperature=0.0,
                 adapter=paths[0])
    sharded.generate([r1])
    assert r0.output_tokens == r1.output_tokens


# ---------------------------------------------------------------------------
# HTTP surface + metrics
# ---------------------------------------------------------------------------

def test_http_adapter_field_and_metrics(world):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg, params, paths, _, _ = world
    app = create_server(cfg, params, max_slots=2, adapter_pool=2, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 3, "temperature": 0.0,
                "adapter": paths[0]})
            assert r.status == 200
            body = await r.json()
            assert body["choices"][0]["finish_reason"] == "length"
            # Unknown adapter -> 400, not a hung engine.
            r = await client.post("/v1/completions", json={
                "prompt": "x", "max_tokens": 2,
                "adapter": "/no/such/adapter"})
            assert r.status == 400
            r = await client.post("/v1/completions", json={
                "prompt": "x", "max_tokens": 2, "adapter": 7})
            assert r.status == 400
            r = await client.get("/metrics")
            text = await r.text()
            assert "serve_adapter_loads_total 1" in text
            assert "serve_adapters_resident 1" in text
            assert 'serve_adapter_requests_total{adapter="' in text
            r = await client.get("/debug/programs")
            body = await r.json()
            assert body["adapters"]["pool_size"] == 2
            assert body["adapters"]["loads"] == 1
    asyncio.run(drive())
    # Pool-less engines export no adapter families (catalog contract:
    # the families exist exactly on pooled engines). Fresh registry: the
    # process-wide one still carries the pooled server's series.
    from runbooks_tpu.obs import metrics as obs_metrics

    obs_metrics.REGISTRY.reset()
    plain = create_server(cfg, params, adapter_pool=0, max_slots=2,
                          warmup=False)

    async def drive_plain():
        async with TestClient(TestServer(plain)) as client:
            r = await client.get("/metrics")
            text = await r.text()
            assert "serve_adapter_loads_total" not in text
    asyncio.run(drive_plain())


# ---------------------------------------------------------------------------
# Controller: validation + shared-engine tenants
# ---------------------------------------------------------------------------

def test_validate_params_adapter_knobs():
    from runbooks_tpu.controller.common import validate_params

    assert validate_params({"adapter_pool": 8, "lora_rank": 16,
                            "adapter_dir": "/srv/adapters"}) is None
    assert validate_params({"adapter": "tenants/a"}) is None
    assert validate_params({"adapterPool": 4}) is None
    assert "adapter_pool" in validate_params({"adapter_pool": -1})
    assert "lora_rank" in validate_params({"adapter_pool": 2,
                                           "lora_rank": 0})
    # Pool-tuning knobs without a pool are spec typos, not silent no-ops.
    assert "only applies" in validate_params({"lora_rank": 8})
    assert "only applies" in validate_params({"adapter_dir": "/srv/a"})
    assert "adapter" in validate_params({"adapter": "  "})
    assert "adapter" in validate_params({"adapter": 3})
    # Fold-at-load and the pool are mutually exclusive serving modes on
    # one Server (tenants reference the pool host via engineRef).
    assert "cannot combine" in validate_params(
        {"adapter": "tenants/a", "adapter_pool": 4})


def test_shared_engine_tenant_reconcile():
    from runbooks_tpu.api import conditions as cond
    from runbooks_tpu.api.types import API_VERSION, Server
    from runbooks_tpu.cloud.base import CommonConfig
    from runbooks_tpu.cloud.local import LocalCloud
    from runbooks_tpu.controller.manager import Ctx, Manager
    from runbooks_tpu.controller.server import ServerReconciler
    from runbooks_tpu.k8s import objects as ko
    from runbooks_tpu.k8s.fake import FakeCluster
    from runbooks_tpu.sci.base import FakeSCI

    client = FakeCluster()
    cloud = LocalCloud(CommonConfig(cluster_name="t",
                                    artifact_bucket_url="file:///tmp/b",
                                    registry_url="r.local:5000"))
    mgr = Manager(Ctx(client=client, cloud=cloud, sci=FakeSCI()),
                  [ServerReconciler()])

    tenant = Server.new("tenant-a", spec={
        "engineRef": "pool-host",
        "params": {"adapter": "tenants/a"}})
    client.create(tenant.obj)
    mgr.reconcile_until_stable()
    cur = Server(client.get(API_VERSION, "Server", "default", "tenant-a"))
    c = ko.get_condition(cur.obj, cond.SERVING)
    assert c["reason"] == cond.REASON_ENGINE_NOT_FOUND

    # Host exists but runs no pool: the tenant's per-request adapter
    # would 400 on every call — surface it.
    host = Server.new("pool-host", spec={
        "image": "img", "model": {"name": "m"}, "params": {}})
    client.create(host.obj)
    mgr.reconcile_until_stable()
    cur = Server(client.get(API_VERSION, "Server", "default", "tenant-a"))
    c = ko.get_condition(cur.obj, cond.SERVING)
    assert c["reason"] == cond.REASON_ENGINE_NO_POOL

    host.obj["spec"]["params"] = {"adapter_pool": 8}
    client.apply(host.obj, "test")
    mgr.reconcile_until_stable()
    cur = Server(client.get(API_VERSION, "Server", "default", "tenant-a"))
    c = ko.get_condition(cur.obj, cond.SERVING)
    assert c["reason"] == cond.REASON_ENGINE_NOT_READY

    # Host flips ready: the tenant serves through it — via a Service
    # aliasing the HOST's replica pods, with NO tenant Deployment.
    hcur = client.get(API_VERSION, "Server", "default", "pool-host")
    hcur.setdefault("status", {})["ready"] = True
    client.update_status(hcur)
    mgr.reconcile_until_stable()
    cur = Server(client.get(API_VERSION, "Server", "default", "tenant-a"))
    assert cur.ready
    c = ko.get_condition(cur.obj, cond.SERVING)
    assert c["status"] == "True"
    svc = client.get("v1", "Service", "default", "tenant-a")
    assert svc["spec"]["selector"] == {"server": "pool-host",
                                      "role": "run"}
    assert client.get("apps/v1", "Deployment", "default",
                      "tenant-a") is None

    # Tenant without an adapter param is invalid, not silently base.
    bad = Server.new("tenant-bad", spec={"engineRef": "pool-host",
                                         "params": {}})
    client.create(bad.obj)
    mgr.reconcile_until_stable()
    cur = Server(client.get(API_VERSION, "Server", "default",
                            "tenant-bad"))
    c = ko.get_condition(cur.obj, cond.SERVING)
    assert c["reason"] == cond.REASON_INVALID_PARAMS

    # A host EVENT fans out to its tenants (DEPENDENT_INDEXES maps the
    # plain-string engineRef): the watch path, without a full resync.
    hcur = client.get(API_VERSION, "Server", "default", "pool-host")
    hcur["status"]["ready"] = False
    client.update_status(hcur)
    mgr._reconcile_dependents("Server", hcur)
    cur = Server(client.get(API_VERSION, "Server", "default", "tenant-a"))
    assert not cur.ready
    c = ko.get_condition(cur.obj, cond.SERVING)
    assert c["reason"] == cond.REASON_ENGINE_NOT_READY

    # Host deletion: the delete event re-reconciles the tenant, which
    # flips to SharedEngineNotFound instead of staying stale-ready.
    client.delete(API_VERSION, "Server", "default", "pool-host")
    mgr._reconcile_dependents("Server", hcur)
    cur = Server(client.get(API_VERSION, "Server", "default", "tenant-a"))
    c = ko.get_condition(cur.obj, cond.SERVING)
    assert c["reason"] == cond.REASON_ENGINE_NOT_FOUND
