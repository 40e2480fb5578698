"""Serving engine + HTTP API tests.

Engine correctness oracle: greedy rollout through the full no-cache forward
must equal the engine's slot-based cached decode.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import forward, init_params
from runbooks_tpu.serve.engine import InferenceEngine, Request


def tiny_cfg():
    return dataclasses.replace(
        get_config("llama2-7b"), vocab_size=128, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=64, dtype="float32",
    )


def greedy_rollout(cfg, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits, _ = forward(cfg, params, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_full_forward_greedy():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=4)

    prompts = [[5, 9, 17], [3, 4, 5, 6, 7, 8, 9, 10], [42]]
    reqs = [Request(prompt_tokens=p, max_tokens=8, temperature=0.0)
            for p in prompts]
    engine.generate(reqs)
    for p, r in zip(prompts, reqs):
        expect = greedy_rollout(cfg, params, p, 8)
        assert r.output_tokens == expect, (p, r.output_tokens, expect)


def test_engine_continuous_batching_mid_flight():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=2)

    r1 = Request(prompt_tokens=[5, 9, 17], max_tokens=10, temperature=0.0)
    r2 = Request(prompt_tokens=[3, 4, 5, 6], max_tokens=10, temperature=0.0)
    engine.submit(r1)
    engine.step()
    engine.step()  # r1 is 2 tokens in
    engine.submit(r2)  # joins mid-flight
    while engine.has_work():
        engine.step()
    assert r1.output_tokens == greedy_rollout(cfg, params, [5, 9, 17], 10)
    assert r2.output_tokens == greedy_rollout(cfg, params, [3, 4, 5, 6], 10)


def test_engine_eos_and_limits():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=2)
    expect = greedy_rollout(cfg, params, [7, 7, 7], 6)
    eos = expect[2]
    r = Request(prompt_tokens=[7, 7, 7], max_tokens=6, temperature=0.0,
                eos_id=eos)
    engine.generate([r])
    assert r.finish_reason == "stop"
    assert r.output_tokens[-1] == eos
    # stops at the FIRST occurrence of eos in the greedy rollout
    assert len(r.output_tokens) == expect.index(eos) + 1

    r2 = Request(prompt_tokens=[7, 7, 7], max_tokens=2, temperature=0.0)
    engine.generate([r2])
    assert r2.finish_reason == "length"
    assert len(r2.output_tokens) == 2


def test_engine_uses_full_capacity():
    # Regression: the length bound used to double-count generated tokens and
    # truncate at ~half capacity.
    cfg = dataclasses.replace(tiny_cfg(), max_seq_len=32)
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=1, max_seq_len=32)
    r = Request(prompt_tokens=[1, 2, 3, 4], max_tokens=100, temperature=0.0)
    engine.generate([r])
    # 28 tokens fill the cache (4 prompt + 28 = 32 slots); the final token
    # is sampled without needing a cache write => 29 outputs total.
    assert len(r.output_tokens) == 32 - 4 + 1
    assert r.finish_reason == "length"


def test_engine_sampled_temperature_varies():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=4, seed=1)
    reqs = [Request(prompt_tokens=[11, 12], max_tokens=12, temperature=2.0,
                    top_k=50)
            for _ in range(3)]
    engine.generate(reqs)
    outs = {tuple(r.output_tokens) for r in reqs}
    assert len(outs) > 1  # high temperature should decorrelate slots


def test_engine_sharded_matches_unsharded():
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    prompts = [[5, 9, 17], [3, 4, 5, 6]]

    plain = InferenceEngine(cfg, params, max_slots=2)
    plain_reqs = [Request(prompt_tokens=list(p), max_tokens=8,
                          temperature=0.0) for p in prompts]
    plain.generate(plain_reqs)

    mesh = make_mesh(MeshConfig(data=1, fsdp=2, sequence=1, tensor=4))
    sharded = InferenceEngine(cfg, params, max_slots=2, mesh=mesh)
    shard_reqs = [Request(prompt_tokens=list(p), max_tokens=8,
                          temperature=0.0) for p in prompts]
    sharded.generate(shard_reqs)

    for a, b in zip(plain_reqs, shard_reqs):
        assert a.output_tokens == b.output_tokens
    # params really are distributed (a TP-sharded layer matrix)
    wq = sharded.params["layers"]["attn"]["wq"]
    assert len({s.device for s in wq.addressable_shards}) == 8


def test_engine_warmup_precompiles_and_resets():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=2)
    engine.warmup()
    assert not engine.active.any() and not engine.queue
    # Generation after warmup still correct.
    r = Request(prompt_tokens=[5, 9, 17], max_tokens=4, temperature=0.0)
    engine.generate([r])
    assert r.output_tokens == greedy_rollout(cfg, params, [5, 9, 17], 4)


def test_worker_crash_containment():
    """An engine failure mid-flight must fail waiting requests with the
    error and leave the worker serving subsequent requests."""
    from runbooks_tpu.serve.api import EngineWorker

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=2)
    worker = EngineWorker(engine)

    boom = {"armed": True}
    orig_step = engine.step

    def exploding_step():
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("synthetic device failure")
        return orig_step()

    engine.step = exploding_step
    fut = worker.submit(Request(prompt_tokens=[1, 2], max_tokens=3,
                                temperature=0.0))
    import pytest as _pytest

    with _pytest.raises(RuntimeError, match="synthetic device failure"):
        fut.result(timeout=30)

    # Worker thread survived; next request succeeds on the reset engine.
    fut2 = worker.submit(Request(prompt_tokens=[1, 2], max_tokens=3,
                                 temperature=0.0))
    done = fut2.result(timeout=60)
    assert len(done.output_tokens) == 3
    worker.stop()


def test_http_api_end_to_end():
    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    app = create_server(cfg, params, max_slots=2, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.get("/")
            assert r.status == 200
            body = await r.json()
            assert body["status"] == "ok"

            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 4, "temperature": 0.0})
            assert r.status == 200
            body = await r.json()
            assert body["object"] == "text_completion"
            assert body["choices"][0]["finish_reason"] in ("length", "stop")
            assert body["usage"]["completion_tokens"] >= 1

            # batch (list) prompt: one choice per element
            r = await client.post("/v1/completions", json={
                "prompt": ["a", "bb"], "max_tokens": 3, "temperature": 0.0})
            assert r.status == 200
            body = await r.json()
            assert [c["index"] for c in body["choices"]] == [0, 1]

            # chat endpoint
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 3, "temperature": 0.0})
            assert r.status == 200
            body = await r.json()
            assert body["object"] == "chat.completion"
            assert body["choices"][0]["message"]["role"] == "assistant"
            r = await client.post("/v1/chat/completions", json={})
            assert r.status == 400

            # malformed requests
            r = await client.post("/v1/completions", json={"max_tokens": 4})
            assert r.status == 400
            r = await client.post("/v1/completions", data=b"{not json")
            assert r.status == 400
            r = await client.post("/v1/completions", json={
                "prompt": "x", "max_tokens": 0})
            assert r.status == 400
            # over-long prompt -> 400, not silent truncation
            r = await client.post("/v1/completions", json={
                "prompt": "x" * 500, "max_tokens": 4})
            assert r.status == 400
            body = await r.json()
            assert "context window" in body["error"]["message"]

    asyncio.run(drive())


def test_http_streaming_sse():
    """`stream: true` returns SSE chunks whose concatenated deltas equal the
    non-streamed completion, ending with a finish chunk and [DONE] (the
    reference's documented server, basaran, streams the same protocol)."""
    import json as _json

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    app = create_server(cfg, params, max_slots=2, warmup=False)

    def parse_sse(raw: str):
        events = []
        for line in raw.split("\n"):
            if line.startswith("data: "):
                payload = line[len("data: "):]
                events.append(payload if payload == "[DONE]"
                              else _json.loads(payload))
        return events

    async def drive():
        async with TestClient(TestServer(app)) as client:
            # Reference answer without streaming (greedy => deterministic).
            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 5, "temperature": 0.0})
            expect = (await r.json())["choices"][0]["text"]

            r = await client.post("/v1/completions", json={
                "prompt": "hello", "max_tokens": 5, "temperature": 0.0,
                "stream": True})
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/event-stream")
            events = parse_sse(await r.text())
            assert events[-1] == "[DONE]"
            chunks = events[:-1]
            assert all(e["object"] == "text_completion" for e in chunks)
            text = "".join(c["choices"][0]["text"] for c in chunks)
            assert text == expect
            finishes = [c["choices"][0]["finish_reason"] for c in chunks]
            assert finishes[-1] in ("length", "stop")
            # more than one delta chunk => actually incremental
            assert len(chunks) >= 2

            # chat streaming: delta format, role announced once
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4, "temperature": 0.0, "stream": True})
            assert r.status == 200
            events = parse_sse(await r.text())
            assert events[-1] == "[DONE]"
            chunks = events[:-1]
            assert all(e["object"] == "chat.completion.chunk"
                       for e in chunks)
            deltas = [c["choices"][0]["delta"] for c in chunks]
            assert any(d.get("content") for d in deltas)
            # the assistant role is announced exactly once, in the first delta
            assert deltas[0].get("role") == "assistant"
            assert sum(1 for d in deltas if "role" in d) == 1

    asyncio.run(drive())


@pytest.mark.slow
@pytest.mark.parametrize("chunk", (3, 4, 8))
def test_engine_chunked_decode_matches_single_step(chunk):
    """decode_chunk>1 (the TPU default: K scan steps per host round-trip)
    must emit token-for-token what chunk=1 stepping emits — including
    requests that hit EOS or max_tokens MID-chunk (device liveness mask) —
    and hand every token over in order (tests/test_decode_deferred.py has
    the order of a tick)."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    prompts = [[5, 9, 17], [3, 4, 5, 6, 7, 8, 9, 10], [42]]
    expect = {tuple(p): greedy_rollout(cfg, params, p, 11) for p in prompts}
    eos = expect[(5, 9, 17)][4]  # force a mid-chunk stop for request 0

    engine = InferenceEngine(cfg, params, max_slots=4, decode_chunk=chunk)
    reqs = [Request(prompt_tokens=list(p), max_tokens=n,
                    temperature=0.0, eos_id=e)
            for p, n, e in [(prompts[0], 11, eos),
                            (prompts[1], 7, None),
                            (prompts[2], 11, None)]]
    streamed = [[] for _ in reqs]
    for r, out in zip(reqs, streamed):
        r.on_token = out.append
    engine.generate(reqs)
    full = expect[tuple(prompts[0])]
    stop_at = full.index(eos) + 1 if eos in full else 11
    assert reqs[0].output_tokens == full[:stop_at]
    if eos in full:
        assert reqs[0].finish_reason == "stop"
    assert reqs[1].output_tokens == expect[tuple(prompts[1])][:7]
    assert reqs[1].finish_reason == "length"
    assert reqs[2].output_tokens == expect[tuple(prompts[2])]
    assert streamed == [r.output_tokens for r in reqs]


def test_engine_chunked_decode_capacity_bound():
    """Out-of-room detection works on device: a chunk never writes past the
    cache even when the request budget would keep going."""
    cfg = dataclasses.replace(tiny_cfg(), max_seq_len=32)
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=1, max_seq_len=32,
                             decode_chunk=8)
    r = Request(prompt_tokens=[1, 2, 3, 4], max_tokens=100, temperature=0.0)
    engine.generate([r])
    assert len(r.output_tokens) == 32 - 4 + 1
    assert r.finish_reason == "length"


@pytest.mark.slow
def test_engine_batched_prefill_mixed_buckets():
    """Admissions in one tick group by length bucket; each group prefills
    as one [rows, bucket] call, and results still match the per-request
    greedy oracle (incl. the power-of-two row padding path: 3 real rows
    in a rows=4 call, plus a second bucket group)."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=8, prefill_budget=1024)
    prompts = [[5, 9, 17], [3, 4], [42],                      # bucket 16
               list(range(2, 22)), list(range(7, 25))]        # bucket 32
    reqs = [Request(prompt_tokens=list(p), max_tokens=6, temperature=0.0)
            for p in prompts]
    for r in reqs:
        engine.submit(r)
    engine.step()  # one tick admits all five (two grouped prefill calls)
    assert int(engine.active.sum()) == 5
    while engine.has_work():
        engine.step()
    for p, r in zip(prompts, reqs):
        assert r.output_tokens == greedy_rollout(cfg, params, p, 6), p


@pytest.mark.slow
def test_engine_bucketed_cache_view_parity():
    """Decode through small cache-read views (the HBM-bandwidth
    optimization) emits exactly what the full-cache read emits, across
    view-bucket transitions as contexts grow."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    engine = InferenceEngine(cfg, params, max_slots=2, decode_chunk=4)
    assert engine.view_buckets == [64]  # tiny cap -> single bucket
    engine.view_buckets = [16, 32, 64]  # force bucket transitions
    prompts = [[5, 9, 17], [3, 4, 5, 6, 7, 8, 9, 10]]
    reqs = [Request(prompt_tokens=list(p), max_tokens=30, temperature=0.0)
            for p in prompts]
    engine.generate(reqs)
    for p, r in zip(prompts, reqs):
        assert r.output_tokens == greedy_rollout(cfg, params, p, 30), p
    # the run actually crossed view buckets (3+30+chunk > 32 > 16)
    assert len(engine._decode_fns) >= 2


def test_engine_prefill_budget_spreads_admission():
    """A burst of prompts is admitted over multiple steps bounded by the
    per-step prefill-token budget (bucket-padded), so in-flight decodes
    keep making progress during the burst; a single over-budget prompt
    still admits alone (no starvation)."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(cfg, params, max_slots=4, prefill_budget=32)

    # 3 prompts of 20 tokens -> bucket 32 each: one admission per step.
    reqs = [Request(prompt_tokens=list(range(1, 21)), max_tokens=10,
                    temperature=0.0) for _ in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    assert int(eng.active.sum()) == 1 and len(eng.queue) == 2
    eng.step()
    assert int(eng.active.sum()) == 2 and len(eng.queue) == 1
    eng.step()
    assert int(eng.active.sum()) == 3 and not eng.queue
    # Earlier admissions kept decoding while later ones waited their turn.
    assert [len(r.output_tokens) for r in reqs] == [4, 3, 2]
    while eng.has_work():
        eng.step()
    assert all(len(r.output_tokens) == 10 for r in reqs)

    # Over-budget single prompt (bucket 64 > 32) admits immediately.
    eng.submit(Request(prompt_tokens=list(range(1, 41)), max_tokens=2,
                       temperature=0.0))
    eng.step()
    assert not eng.queue  # admitted despite exceeding the budget

    # Short prompts (bucket 16) pack two-per-step under the same budget.
    while eng.has_work():
        eng.step()
    for _ in range(4):
        eng.submit(Request(prompt_tokens=[1, 2, 3], max_tokens=10,
                           temperature=0.0))
    eng.step()
    assert int(eng.active.sum()) == 2 and len(eng.queue) == 2


@pytest.mark.slow
def test_engine_shared_prefix_reuse_matches_full_prefill():
    """Requests whose prompt starts with a registered prefix must produce
    EXACTLY the tokens a full prefill would (the cached prefix K/V plus a
    suffix-only scatter prefill is numerically the same computation), and
    the engine must actually reuse the prefix (prefix_tokens_reused)."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prefix = [int(t) for t in rng.integers(1, cfg.vocab_size, 16)]
    suffixes = [[7, 9], [11], [3, 5, 8, 13]]

    ref = InferenceEngine(cfg, params, max_slots=4)
    reqs_ref = [Request(prompt_tokens=prefix + s, max_tokens=8)
                for s in suffixes]
    ref.generate(reqs_ref)

    eng = InferenceEngine(cfg, params, max_slots=4)
    assert eng.register_prefix(prefix) == 16
    reqs = [Request(prompt_tokens=prefix + s, max_tokens=8)
            for s in suffixes]
    eng.generate(reqs)

    assert eng.prefix_tokens_reused == 16 * len(suffixes)
    for got, want in zip(reqs, reqs_ref):
        assert got.output_tokens == want.output_tokens, (
            got.output_tokens, want.output_tokens)


@pytest.mark.slow
def test_engine_prefix_register_rounds_and_evicts():
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(cfg, params, max_slots=2)
    # Too short to cache.
    assert eng.register_prefix([1, 2, 3]) == 0
    # 19 tokens round down to 16.
    toks = list(range(1, 20))
    assert eng.register_prefix(toks) == 16
    # Re-registration is a cache hit (no growth).
    assert eng.register_prefix(toks) == 16
    assert len(eng._prefix_cache) == 1
    # LRU bound holds.
    for i in range(eng.prefix_cache_size + 1):
        eng.register_prefix([100 + i] * 16)
    assert len(eng._prefix_cache) == eng.prefix_cache_size


@pytest.mark.slow
def test_engine_prefix_mixed_with_plain_requests():
    """A tick admitting both prefix-hit and plain requests splits into
    separate prefill groups and all outputs match the no-prefix engine."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    prefix = list(range(2, 18))
    prompts = [prefix + [40, 41], [9, 8, 7], prefix + [50]]

    ref = InferenceEngine(cfg, params, max_slots=4)
    reqs_ref = [Request(prompt_tokens=p, max_tokens=6) for p in prompts]
    ref.generate(reqs_ref)

    eng = InferenceEngine(cfg, params, max_slots=4)
    eng.register_prefix(prefix)
    reqs = [Request(prompt_tokens=p, max_tokens=6) for p in prompts]
    eng.generate(reqs)
    assert eng.prefix_tokens_reused == 32
    for got, want in zip(reqs, reqs_ref):
        assert got.output_tokens == want.output_tokens


def test_http_prefix_registration_endpoint():
    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    app = create_server(cfg, params, max_slots=2, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            toks = list(range(2, 22))
            r = await client.post("/v1/prefix", json={"tokens": toks})
            assert r.status == 200
            assert (await r.json())["cached_prefix_len"] == 16

            # A completion whose prompt starts with the prefix reuses it.
            eng = app["worker"].engine
            before = eng.prefix_tokens_reused
            req = Request(prompt_tokens=toks[:16] + [30, 31], max_tokens=3)
            fut = app["worker"].submit(req)
            await asyncio.wrap_future(fut)
            assert eng.prefix_tokens_reused == before + 16

            r = await client.post("/v1/prefix", json={"tokens": "nope"})
            assert r.status == 400
            r = await client.get("/metrics")
            assert "serve_prefix_tokens_reused_total 16" in await r.text()

    asyncio.run(drive())


@pytest.mark.slow
def test_engine_prefix_in_use_survives_eviction_pressure():
    """Admission hits refresh the LRU: the prefix serving live traffic
    must outlive later registrations."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    eng = InferenceEngine(cfg, params, max_slots=2)
    hot = list(range(2, 18))
    eng.register_prefix(hot)
    # Traffic keeps hitting the hot prefix while cold prefixes register
    # past the cache bound; each admission hit refreshes its LRU slot.
    for i in range(eng.prefix_cache_size):
        eng.generate([Request(prompt_tokens=hot + [30 + i], max_tokens=2)])
        eng.register_prefix([100 + i] * 16)
    assert eng.prefix_tokens_reused == 16 * eng.prefix_cache_size
    assert tuple(hot) in eng._prefix_cache, "hot prefix was evicted"


def test_engine_register_prefix_from_slot_matches_full_prefill():
    """Zero-forward prefix registration: KV copied out of a finished
    request's slot must serve later longer prompts with EXACTLY the
    outputs a full prefill produces."""
    cfg = tiny_cfg()
    params = init_params(cfg, jax.random.key(0))
    turn1 = list(range(2, 20))            # 18 tokens -> bucket 16 cached
    turn2 = turn1 + [30, 31, 32]

    ref = InferenceEngine(cfg, params, max_slots=2)
    want = Request(prompt_tokens=list(turn2), max_tokens=6)
    ref.generate([want])

    eng = InferenceEngine(cfg, params, max_slots=2)
    first = Request(prompt_tokens=list(turn1), max_tokens=4)
    eng.generate([first])
    assert first._slot >= 0
    assert eng.register_prefix_from_slot(first._slot, turn1) == 16
    got = Request(prompt_tokens=list(turn2), max_tokens=6)
    eng.generate([got])
    assert eng.prefix_tokens_reused == 16
    assert got.output_tokens == want.output_tokens


def test_http_chat_auto_prefix_multi_turn():
    """auto_prefix_chat: turn N's prompt KV is registered from its slot
    and turn N+1 (whose rendered prompt extends it) reuses it, with
    identical answers to a server without the feature."""
    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg = dataclasses.replace(tiny_cfg(), max_seq_len=256)
    params = init_params(cfg, jax.random.key(0))

    async def converse(app):
        msgs = [{"role": "system",
                 "content": "Be concise and always answer in English."},
                {"role": "user", "content": "hello there"}]
        answers = []
        async with TestClient(TestServer(app)) as client:
            for turn in range(2):
                r = await client.post("/v1/chat/completions", json={
                    "messages": msgs, "max_tokens": 4, "temperature": 0.0})
                assert r.status == 200
                body = await r.json()
                text = body["choices"][0]["message"]["content"]
                answers.append(text)
                msgs.append({"role": "assistant", "content": text})
                msgs.append({"role": "user", "content": "and again"})
            # Worker registers from the slot after each completion; by
            # the second turn the first turn's prompt must have been
            # reused (rendered history strictly extends it).
            eng = app["worker"].engine
            return answers, eng.prefix_tokens_reused

    app_off = create_server(cfg, params, max_slots=2, warmup=False)
    want, reused_off = asyncio.run(converse(app_off))
    assert reused_off == 0

    app_on = create_server(cfg, params, max_slots=2, auto_prefix_chat=True,
                           warmup=False)
    got, reused_on = asyncio.run(converse(app_on))
    assert reused_on > 0, "second turn did not reuse the first turn's KV"
    assert got == want
