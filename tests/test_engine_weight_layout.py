"""The engine places the weights it was handed in the layouts its decode
program asks for, once, at construction (serve/weight_layout.py,
engine._place_weights). What the TPU's compiler asks for, and what the
placed programs then copy, is tests/test_decode_in_place.py's (compiled
for a described chip); here, on the CPU, is the behaviour: a placement
changes no token, compiles nothing more in the warm-up and nothing under
traffic, keeps shardings, survives a layout that cannot be applied, and
shows in the census, on /metrics and in the trace.
"""

import dataclasses
import functools
import json

import jax
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import forward, init_params
from runbooks_tpu.obs import device as obs_device
from runbooks_tpu.obs import trace as obs_trace
from runbooks_tpu.serve import engine as engine_mod
from runbooks_tpu.serve import weight_layout
from runbooks_tpu.serve.engine import InferenceEngine, Request
from runbooks_tpu.serve.paging import PagedInferenceEngine

MAX_LEN = 48


def tiny_cfg(**over):
    base = dict(vocab_size=258, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                max_seq_len=MAX_LEN, dtype="float32")
    base.update(over)
    return dataclasses.replace(get_config("llama2-7b"), **base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg, jax.random.key(0))


def turned(leaf, order=None) -> Format:
    """The leaf's format with its two minor-most dimensions swapped (or
    the given major-to-minor order)."""
    order = order or (*range(leaf.ndim - 2), leaf.ndim - 1, leaf.ndim - 2)
    return Format(Layout(tuple(order)), leaf.sharding)


def ask_for(monkeypatch, **asked):
    """Make the engine's question come back with the client's formats but
    for the leaves named (`embed`, `mlp.wo` ...): a function of the leaf
    gives each one's asked-for format. The seam is the one function the
    engine asks through."""
    real = weight_layout.asked_formats

    def asked_formats(fn, params, *args, **jit_kwargs):
        real(fn, params, *args, **jit_kwargs)    # the question still compiles
        flat = jax.tree_util.tree_leaves_with_path(params)
        out = []
        for path, leaf in flat:
            name = ".".join(str(getattr(k, "key", k)) for k in path)
            make = next((f for key, f in asked.items()
                         if name.endswith(key.replace("_", "."))), None)
            out.append(make(leaf) if make else leaf.format)
        return out

    monkeypatch.setattr(engine_mod, "asked_formats", asked_formats)


def mixed_requests():
    """Five requests on two slots: prefill, at least three decode chunks a
    request, slots that change hands, a sampled row."""
    return [Request(prompt_tokens=[5, 9, 17], max_tokens=14),
            Request(prompt_tokens=[3, 4, 5, 6, 7, 8], max_tokens=11),
            Request(prompt_tokens=[42, 7], max_tokens=13, temperature=0.9,
                    top_k=12),
            Request(prompt_tokens=[8, 8, 8, 9], max_tokens=10),
            Request(prompt_tokens=[60, 61], max_tokens=12)]


def served(engine):
    return [(r.output_tokens, r.finish_reason)
            for r in engine.generate(mixed_requests())]


@pytest.mark.parametrize("kind", ["dense", "paged"])
def test_a_placement_changes_no_token(model, monkeypatch, kind):
    cfg, params = model
    make = (InferenceEngine if kind == "dense" else
            functools.partial(PagedInferenceEngine, page_size=16))
    want = served(make(cfg, params, max_slots=2, decode_chunk=3))
    # One 2-d leaf and one stacked leaf in another layout than the client's.
    ask_for(monkeypatch, embed=turned, mlp_wo=turned)
    eng = make(cfg, params, max_slots=2, decode_chunk=3)
    assert eng.weight_layout["leaves_replaced"] == 2
    assert eng.weight_layout["bytes_replaced"] == (
        params["embed"].nbytes + params["layers"]["mlp"]["wo"].nbytes)
    assert eng.params["embed"].format.layout.major_to_minor == (1, 0)
    assert eng.params["layers"]["mlp"]["wo"].format.layout.major_to_minor \
        == (0, 2, 1)
    # The leaves nobody asked about are the very objects handed in.
    assert eng.params["layers"]["attn"]["wq"] is params["layers"]["attn"]["wq"]
    assert served(eng) == want
    # ... and the caller's tree is as it was: nothing was donated.
    assert params["embed"].format.layout.major_to_minor == (0, 1)
    logits, _ = forward(cfg, params, np.asarray([[5, 9, 17]], np.int32))
    assert np.isfinite(np.asarray(logits)).all()


def test_warmup_compiles_what_it_did_and_traffic_compiles_nothing(
        model, monkeypatch):
    cfg, params = model
    sentinel = obs_device.SENTINEL
    if not sentinel.install():
        pytest.skip("jax.monitoring unavailable; sentinel cannot verify")
    # (The first warm-up of a process also compiles a few one-off fills.)
    plain = InferenceEngine(cfg, params, max_slots=2, decode_chunk=3)
    plain.warmup()
    ask_for(monkeypatch, embed=turned, attn_wq=turned)
    eng = InferenceEngine(cfg, params, max_slots=2, decode_chunk=3)
    eng.warmup()
    try:
        # The prefill shapes admission can dispatch (engine.
        # dispatch_shapes: a row alone in each bucket, two only where the
        # tick's budget holds two of the bucket) and one decode view,
        # placed or not: the question's own compile is the constructor's,
        # not the warm-up's.
        n = len(eng.dispatch_shapes) + 1
        assert n < 2 * len(eng.prefill_buckets) + 1
        assert plain.warmup_census["compiles"] >= n
        assert eng.warmup_census["compiles"] == n
        assert eng.warmup_census["weight_layout"] == eng.weight_layout
        assert eng.weight_layout["leaves_replaced"] == 2
        total, unexpected = sentinel.total, sentinel.unexpected
        served(eng)
        assert sentinel.total == total, "compiled under traffic"
        assert sentinel.unexpected == unexpected
    finally:
        for engine in (plain, eng):
            engine.release_steady()


def test_a_layout_that_cannot_be_applied_is_kept_and_counted(
        model, monkeypatch, capsys):
    cfg, params = model
    want = served(InferenceEngine(cfg, params, max_slots=2, decode_chunk=3))
    # A major-to-minor order of the wrong rank: the placement raises.
    ask_for(monkeypatch, embed=turned,
            attn_wo=lambda leaf: turned(leaf, (0, 1)))
    eng = InferenceEngine(cfg, params, max_slots=2, decode_chunk=3)
    assert eng.weight_layout["leaves_replaced"] == 1
    assert eng.weight_layout["leaves_kept"] == 1
    assert eng.weight_layout["why"]
    assert "1 leaves kept where they were" in capsys.readouterr().out
    assert eng.params["layers"]["attn"]["wo"] \
        is params["layers"]["attn"]["wo"]
    assert served(eng) == want


def test_a_backend_that_cannot_answer_leaves_the_weights_alone(
        model, monkeypatch):
    cfg, params = model

    def no_answer(*args, **kwargs):
        raise NotImplementedError("no layouts on this backend")

    monkeypatch.setattr(engine_mod, "asked_formats", no_answer)
    eng = InferenceEngine(cfg, params, max_slots=2, decode_chunk=3)
    assert eng.weight_layout["leaves_replaced"] == 0
    assert "NotImplementedError" in eng.weight_layout["why"]
    assert all(a is b for a, b in zip(jax.tree.leaves(eng.params),
                                      jax.tree.leaves(params)))
    assert served(eng)[0][0]


def test_place_lets_go_of_a_source_before_the_next_leaf(model):
    cfg, params = model
    leaves = [params["embed"] + 0, params["layers"]["mlp"]["wo"] + 0,
              params["layers"]["attn"]["wo"] + 0]
    alive_at_put = []

    def put(leaf, fmt):
        alive_at_put.append(sum(not a.is_deleted() for a in sources))
        return jax.device_put(leaf, fmt)

    import weakref
    sources = list(leaves)
    refs = [weakref.ref(a) for a in sources]
    wanted = [turned(a) for a in leaves[:2]] + [leaves[2].format]
    del sources[:]
    done = weight_layout.place(leaves, wanted, put=put)
    assert (done.leaves_replaced, done.leaves_kept) == (2, 0)
    # The list was the only holder: both sources are gone, the leaf that
    # agreed is the object it was.
    assert refs[0]() is None and refs[1]() is None
    assert refs[2]() is leaves[2]
    assert len(alive_at_put) == 2


def test_shardings_are_kept_under_a_mesh(model, monkeypatch):
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg, params = model
    mesh = make_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
    plain = InferenceEngine(cfg, params, max_slots=2, decode_chunk=3,
                            mesh=mesh)
    want = served(plain)
    before = jax.tree.map(lambda a: a.sharding, plain.params)
    ask_for(monkeypatch, embed=turned, attn_wq=turned, mlp_wo=turned)
    eng = InferenceEngine(cfg, params, max_slots=2, decode_chunk=3,
                          mesh=mesh)
    assert eng.weight_layout["leaves_replaced"] >= 3
    after = jax.tree.map(lambda a: a.sharding, eng.params)
    assert jax.tree.leaves(after) == jax.tree.leaves(before)
    assert eng.params["layers"]["attn"]["wq"].format.layout.major_to_minor \
        == (0, 2, 1)
    assert served(eng) == want


def test_gauges_census_line_and_span(model, monkeypatch, tmp_path, capsys):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server
    from runbooks_tpu.train.data import ByteTokenizer

    from runbooks_tpu.obs import metrics as obs_metrics

    def flash_gauges(rendered):
        return sorted(ln for ln in rendered.splitlines() if ln.startswith(
            ("serve_flash_heads_per_step", "serve_flash_block_shape")))

    cfg, params = model
    flash_before = flash_gauges(obs_metrics.REGISTRY.render())
    ask_for(monkeypatch, embed=turned, mlp_wo=turned)
    monkeypatch.setenv("RBT_TRACE", "1")
    obs_trace.configure(str(tmp_path / "trace.jsonl"))
    try:
        app = create_server(cfg, params, ByteTokenizer(), max_slots=2,
                            decode_chunk=3, warmup=True)
    finally:
        obs_trace.close()
        obs_trace.configure(None)
    engine = app["worker"].engine

    async def scrape():
        async with TestClient(TestServer(app)) as client:
            programs = await (await client.get("/debug/programs")).json()
            return await (await client.get("/metrics")).text(), programs

    try:
        text, programs = asyncio.run(scrape())
    finally:
        app["worker"].stop()
    values = {ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines() if ln.startswith("serve_weight_")}
    moved = params["embed"].nbytes + params["layers"]["mlp"]["wo"].nbytes
    assert values == {"serve_weight_leaves_replaced": 2.0,
                      "serve_weight_bytes_replaced": float(moved)}
    assert programs["warmup_census"]["weight_layout"] == engine.weight_layout
    # No prefill of this engine takes the flash path (XLA off the TPU).
    assert programs["warmup_census"]["flash_head_block"] == {}
    assert programs["warmup_census"]["flash_blocks"] == {}
    # (The registry is the process's: a test file that ran before this one
    # in the same worker may have left a flash engine's gauges in it.)
    assert flash_gauges(text) == flash_before
    assert f"'bytes_replaced': {moved}" in capsys.readouterr().out
    events = [json.loads(ln.rstrip(",")) for ln in
              (tmp_path / "trace.jsonl").read_text().splitlines()
              if "startup.weight_layout" in ln]
    assert len(events) == 1
    assert events[0]["args"] == {"leaves_replaced": 2,
                                 "bytes_replaced": moved}


def test_the_relayout_is_compiled_never_loaded(model, monkeypatch):
    """On a TPU the persistent cache hands back, for the identity whose
    result has a layout of its own, an executable that gives the default
    layout: the put runs with the cache off and leaves it as it was."""
    cfg, params = model
    seen = []
    real = jax.device_put

    def device_put(x, fmt):
        seen.append(jax.config.jax_enable_compilation_cache)
        return real(x, fmt)

    monkeypatch.setattr(weight_layout.jax, "device_put", device_put)
    before = jax.config.jax_enable_compilation_cache
    leaves = [params["embed"]]
    done = weight_layout.place(leaves, [turned(params["embed"])])
    assert done.leaves_replaced == 1 and seen == [False]
    assert jax.config.jax_enable_compilation_cache == before
