"""Sharded multi-chip serving mesh (docs/tensor-parallel-performance.md).

Parity discipline, as everywhere in the serving tests: the sharded
engine is an OPTIMIZATION, so a mesh_tensor=2 engine must be
token-for-token identical to the single-device engine on greedy
decode — dense, paged, speculative, and the multi-tenant LoRA pool.
The harness pins 8 virtual CPU devices (conftest) and exact matmul
precision, so parity is byte-exact: the mesh shards the SAME program
(GSPMD inserts the collectives; the math is unchanged).

Compile discipline rides along: a mesh engine's warmup must cover the
full program set so steady-state traffic under the mesh triggers ZERO
unexpected compiles (the census baseline carries *_sharded entries for
exactly these programs).
"""

import dataclasses
import os

import jax
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import init_params
from runbooks_tpu.serve.engine import InferenceEngine, Request
from runbooks_tpu.serve.paging import PagedInferenceEngine
from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh


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


@pytest.fixture(scope="module")
def mesh():
    """tensor=2 serving mesh over the 8-device CPU harness (fsdp soaks
    the rest, like a real single-host slice would)."""
    return make_mesh(MeshConfig(data=1, fsdp=4, tensor=2))


PROMPTS = [[5, 9, 17], [3, 4, 5, 6, 7], [40, 2], [8, 8, 8, 9]]
REP_PROMPT = [5, 6, 7, 8] * 5 + [5, 6]


def greedy_reqs(prompts, max_tokens=8, **kw):
    return [Request(prompt_tokens=list(p), max_tokens=max_tokens,
                    temperature=0.0, **kw) for p in prompts]


def outputs(engine, reqs):
    engine.generate(reqs)
    return [r.output_tokens for r in reqs]


# ---------------------------------------------------------------------------
# Greedy parity: single-device vs mesh_tensor=2
# ---------------------------------------------------------------------------

def test_mesh_parity_dense(model, mesh):
    cfg, params = model
    want = outputs(InferenceEngine(cfg, params, max_slots=2),
                   greedy_reqs(PROMPTS[:2]))
    got = outputs(InferenceEngine(cfg, params, max_slots=2, mesh=mesh),
                  greedy_reqs(PROMPTS[:2]))
    assert got == want
    # weights actually sharded: attention heads split over tensor
    eng = InferenceEngine(cfg, params, max_slots=2, mesh=mesh)
    wq = eng.params["layers"]["attn"]["wq"]
    assert "tensor" in jax.tree.leaves(wq)[0].sharding.spec


def test_mesh_parity_paged(model, mesh):
    cfg, params = model
    want = outputs(
        PagedInferenceEngine(cfg, params, max_slots=2, page_size=16),
        greedy_reqs(PROMPTS[:2]))
    eng = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                               mesh=mesh)
    got = outputs(eng, greedy_reqs(PROMPTS[:2]))
    assert got == want
    # the pool shards kv-heads over tensor; page tables stay host-side
    assert "tensor" in eng.cache.k.sharding.spec


def test_mesh_parity_paged_prefix_sharing(model, mesh):
    """Radix prefix hits splice SHARDED prefix pages into a sharded
    pool — the host-side page tables are oblivious to the mesh."""
    cfg, params = model
    shared = list(range(1, 33))
    prompts = [shared + [40 + i] for i in range(3)]

    def run(mesh_):
        eng = PagedInferenceEngine(cfg, params, max_slots=2,
                                   page_size=16, mesh=mesh_)
        eng.register_prefix(shared)
        return outputs(eng, greedy_reqs(prompts, max_tokens=5))

    assert run(mesh) == run(None)


def test_mesh_parity_speculative(model, mesh):
    cfg, params = model
    prompts = [REP_PROMPT, PROMPTS[1]]
    want = outputs(
        PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                             speculative="off"),
        greedy_reqs(prompts, max_tokens=12))
    on = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                              mesh=mesh, speculative="ngram",
                              draft_tokens=4)
    got = outputs(on, greedy_reqs(prompts, max_tokens=12))
    assert got == want
    # the [B, K+1] verify actually ran under the mesh
    assert on.spec_drafted > 0


def test_mesh_parity_lora_pool(model, mesh, tmp_path):
    """Four distinct tenants on ONE mesh-sharded pooled engine ==
    the single-device pooled engine, token for token (the adapter pool
    shards its lanes by the same logical axes as the base weights)."""
    from runbooks_tpu.serve.lora_pool import save_adapter
    from runbooks_tpu.train.lora import LoraConfig, init_lora

    cfg, params = model
    paths = []
    for i in range(4):
        lora = init_lora(params, LoraConfig(rank=4, alpha=8.0),
                         jax.random.key(10 + i))
        lora = jax.tree.map(
            lambda x, i=i: x + 0.03 * jax.random.normal(
                jax.random.key(20 + i), x.shape, x.dtype), lora)
        path = os.path.join(str(tmp_path), f"tenant{i}")
        save_adapter(path, lora, rank=4, alpha=8.0)
        paths.append(path)

    def reqs():
        return [Request(prompt_tokens=list(p), max_tokens=8,
                        temperature=0.0, adapter=a)
                for p, a in zip(PROMPTS, paths)]

    want = outputs(
        PagedInferenceEngine(cfg, params, max_slots=4, page_size=16,
                             adapter_pool=4, lora_rank=8),
        reqs())
    eng = PagedInferenceEngine(cfg, params, max_slots=4, page_size=16,
                               adapter_pool=4, lora_rank=8, mesh=mesh)
    got = outputs(eng, reqs())
    assert got == want


def test_mesh_collective_matmul_auto(model, mesh):
    """collective_matmul: auto resolves ON under the serving mesh and
    still decodes to finished requests (ring reorders the float
    accumulation, so the oracle here is completion + output length,
    not byte parity — docs/tensor-parallel-performance.md)."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, collective_matmul="auto")
    eng = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                               mesh=mesh)
    reqs = greedy_reqs(PROMPTS[:2], max_tokens=6)
    eng.generate(reqs)
    assert all(r.finished for r in reqs)
    assert all(len(r.output_tokens) == 6 for r in reqs)


def test_load_model_lays_weights_out_on_the_mesh(mesh, monkeypatch,
                                                tmp_path):
    """serve/api.load_model with a serving mesh returns the weights already
    sharded the way the engine shards them, with the same values as the
    unsharded load, and leaves no unsharded copy behind (on four chips that
    copy was 9.2 GB on device 0 and failed the first large prefill)."""
    from runbooks_tpu.serve.api import load_model

    monkeypatch.setenv("RBT_CONTENT_DIR", str(tmp_path))
    spec = {"model": "debug", "seed": 3, "model_overrides": {
        "num_layers": 2, "hidden_size": 64, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "vocab_size": 128}}
    _, plain = load_model(spec)
    cfg, sharded = load_model(spec, mesh)
    wq = sharded["layers"]["attn"]["wq"]
    assert wq.sharding.mesh.shape == mesh.shape
    assert "tensor" in jax.tree.leaves(tuple(wq.sharding.spec))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(sharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # The engine's own placement is then a no-op: same buffers.
    engine = InferenceEngine(cfg, sharded, max_slots=2, mesh=mesh)
    assert engine.params["layers"]["attn"]["wq"] is wq


# ---------------------------------------------------------------------------
# Compile discipline under the mesh
# ---------------------------------------------------------------------------

def test_mesh_zero_unexpected_compiles_in_steady_loop(model, mesh):
    from runbooks_tpu.obs import device as obs_device

    cfg, params = model
    engine = PagedInferenceEngine(cfg, params, max_slots=2,
                                  page_size=16, mesh=mesh)
    try:
        engine.warmup()
        sentinel = obs_device.SENTINEL
        before = sentinel.unexpected
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


def test_mesh_dense_engine_zero_unexpected_compiles(model, mesh):
    """The dense engine under a mesh: the first prefill after warmup (which
    ends in reset(), i.e. a FRESH pool) must hit a warmup-compiled program.
    A pool whose index scalar was left uncommitted keyed a second jit
    entry and recompiled under traffic (found by chip_smoke's four-chip
    dry run)."""
    from runbooks_tpu.obs import device as obs_device

    cfg, params = model
    engine = InferenceEngine(cfg, params, max_slots=2, mesh=mesh)
    try:
        engine.warmup()
        before = obs_device.SENTINEL.unexpected
        engine.generate(greedy_reqs(PROMPTS, max_tokens=3))
        assert obs_device.SENTINEL.unexpected == before, \
            obs_device.SENTINEL.recent_unexpected()
    finally:
        engine.release_steady()


def test_mesh_host_tier_swap_and_preemption_zero_compiles(model, mesh):
    """Host KV tier + QoS preemption under tensor=2: eviction demotes
    each chip's kv-head shard of the page to the host buffer, a
    returning match swaps it back in, and a batch slot preempts for an
    interactive queue head — token-for-token identical to the
    single-device engine, with zero unexpected compiles across the
    swap-out, swap-in, and preemption-resume paths
    (docs/paged-kv.md "Host tier and preemption")."""
    from runbooks_tpu.obs import device as obs_device

    cfg, params = model
    shared = list(range(1, 33))

    def run(mesh_):
        engine = PagedInferenceEngine(cfg, params, max_slots=1,
                                      page_size=16, num_pages=5,
                                      kv_host_pages=8, preemption="swap",
                                      decode_chunk=2, mesh=mesh_)
        engine.warmup()
        sentinel = obs_device.SENTINEL
        before = sentinel.unexpected
        try:
            engine.register_prefix(shared)
            # demote both (sharded) prefix pages to host RAM
            assert engine.pager.radix.evict(10 ** 6) == 2
            ret = Request(prompt_tokens=shared + [50], max_tokens=5,
                          temperature=0.0)
            engine.submit(ret)        # admission swaps the prefix back in
            while not ret.finished:
                engine.step()
            batch = Request(prompt_tokens=list(shared), max_tokens=16,
                            temperature=0.0, priority="batch")
            engine.submit(batch)
            for _ in range(3):        # admit + decode a few tokens
                engine.step()
            inter = Request(prompt_tokens=list(range(90, 106)),
                            max_tokens=8, temperature=0.0,
                            priority="interactive")
            engine.submit(inter)      # displaces the batch slot
            while engine.has_work():
                engine.step()
            assert engine.pager.radix.pages_swapped_out >= 2
            assert engine.pager.pages_swapped_in >= 2
            assert engine.preemptions == 1 == engine.preempted_resumed
            assert sentinel.unexpected == before, \
                sentinel.recent_unexpected()
            return [ret.output_tokens, batch.output_tokens,
                    inter.output_tokens]
        finally:
            engine.release_steady()

    assert run(mesh) == run(None)


# ---------------------------------------------------------------------------
# Per-device HBM accounting
# ---------------------------------------------------------------------------

def test_mesh_kv_occupancy_per_device_bytes(model, mesh):
    cfg, params = model
    plain = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16)
    occ = plain.kv_occupancy()
    # unsharded: per-device == aggregate
    assert occ["kv_pool_bytes_per_device"] == occ["kv_pool_bytes"]
    eng = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                               mesh=mesh)
    occ = eng.kv_occupancy()
    # tensor=2 halves each chip's share of the kv-head-sharded pool
    assert occ["kv_pool_bytes_per_device"] * 2 == occ["kv_pool_bytes"]
    assert occ["bytes_per_page_per_device"] * 2 == occ["bytes_per_page"]


# ---------------------------------------------------------------------------
# Mesh-geometry validation: precise, named constraints
# ---------------------------------------------------------------------------

def test_mesh_geometry_validation(model):
    cfg, params = model
    with pytest.raises(ValueError, match="jax.sharding.Mesh"):
        PagedInferenceEngine(cfg, params, max_slots=2, mesh=object())
    bad = make_mesh(MeshConfig(data=1, fsdp=1, tensor=8))
    with pytest.raises(ValueError,
                       match="kv-heads not divisible by mesh_tensor"):
        PagedInferenceEngine(cfg, params, max_slots=2, mesh=bad)


def test_controller_mesh_param_validation():
    from runbooks_tpu.controller.common import validate_params

    assert validate_params({"mesh_tensor": 4}) is None
    assert validate_params({"mesh_tensor": 2, "mesh_fsdp": -1}) is None
    assert "unknown mesh axis" in validate_params({"mesh_tensro": 2})
    assert "not an integer" in validate_params({"mesh_tensor": "two"})
    assert ">= 1" in validate_params({"mesh_tensor": 0})
    assert "at most one mesh axis" in validate_params(
        {"mesh_tensor": -1, "mesh_fsdp": -1})


def test_controller_server_mesh_geometry():
    from runbooks_tpu.api.types import Server
    from runbooks_tpu.controller.server import _validate_serve_mesh

    def srv(params, tpu=None):
        spec = {"params": params}
        if tpu:
            spec["resources"] = {"tpu": tpu}
        return Server({"kind": "Server",
                       "metadata": {"name": "s", "namespace": "d"},
                       "spec": spec})

    # pipeline stages are a training axis
    assert "mesh_stage" in _validate_serve_mesh(
        srv({"mesh_stage": 2}))
    # malformed tpu block surfaces as a condition, not a crash-loop
    assert "spec.resources.tpu" in _validate_serve_mesh(
        srv({"mesh_tensor": 2}, {"type": "v5p", "topology": "bogus"}))
    # mesh product must match the slice's chips
    assert "provides" in _validate_serve_mesh(
        srv({"mesh_tensor": 2},
            {"type": "v5p", "topology": "2x2x1"}))
    assert _validate_serve_mesh(
        srv({"mesh_tensor": 4},
            {"type": "v5p", "topology": "2x2x1"})) is None
    # -1 fill adapts to whatever the slice provides
    assert _validate_serve_mesh(
        srv({"mesh_tensor": 2, "mesh_fsdp": -1},
            {"type": "v5p", "topology": "2x2x1"})) is None
    # a mesh replica is one process: multi-host slices are out
    assert "hosts" in _validate_serve_mesh(
        srv({"mesh_tensor": 8}, {"type": "v5e", "topology": "4x4"}))
