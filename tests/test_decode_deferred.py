"""A decoded chunk in two halves (serve/engine.py _decode_chunk_step): the
slot half before the next dispatch, the delivery half while it runs; and
the decode carry and per-slot operands that stay on the device.

The reference is the order before: every chunk's tokens handed over before
the next dispatch, every chunk's operands placed from the host
(`SerialEngine`, the same routines called earlier). Tokens, their order a
request, finish reasons and the rng must not differ; only *when* the host
hands a token over may.
"""

import dataclasses
import time

import jax
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import forward, init_params
from runbooks_tpu.obs import device as obs_device
from runbooks_tpu.serve.engine import (
    ROW_ALIVE,
    ROW_POS,
    InferenceEngine,
    Request,
)
from runbooks_tpu.serve.paging import PagedInferenceEngine
from runbooks_tpu.train.data import ByteTokenizer

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


class SerialEngine(InferenceEngine):
    """The order before this split: a chunk's tokens are handed over right
    after its slot half, and no chunk reuses the device's carry."""

    def _take_chunk(self, pulled):
        super()._take_chunk(pulled)
        self.deliver_parked()
        return False


def greedy(cfg, params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        logits, _ = forward(cfg, params, np.asarray([toks], np.int32))
        toks.append(int(np.argmax(logits[0, -1])))
    return toks[len(prompt):]


def mixed_batch(cfg, params):
    """Seven requests on four slots: an EOS inside a chunk, max_tokens=1,
    a row that runs out of cache, sampled rows, slots that change hands."""
    eos_prompt = [5, 9, 17]
    eos = greedy(cfg, params, eos_prompt, 6)[5]
    return [
        Request(prompt_tokens=eos_prompt, max_tokens=20, eos_id=eos),
        Request(prompt_tokens=[3, 4, 5, 6, 7, 8], max_tokens=1),
        Request(prompt_tokens=list(range(1, MAX_LEN - 5)), max_tokens=40),
        Request(prompt_tokens=[42, 7], max_tokens=13, temperature=0.9,
                top_k=12),
        Request(prompt_tokens=[11, 12, 13], max_tokens=9, temperature=1.3,
                top_p=0.8),
        Request(prompt_tokens=[8, 8, 8, 9], max_tokens=10, temperature=0.7),
        Request(prompt_tokens=[60, 61], max_tokens=6),
    ]


def hear(reqs):
    """Record what the outside sees: the on_token calls of each request,
    and the requests that read as finished while one was made, or whose
    on_finish came before their last token."""
    heard = [[] for _ in reqs]
    early = []
    for i, r in enumerate(reqs):
        def on_token(tok, i=i, r=r):
            heard[i].append(tok)
            if r.finished:
                early.append(i)

        def on_finish(req, i=i):
            if not req.finished or heard[i] != req.output_tokens:
                early.append(i)

        r.on_token, r.on_finish = on_token, on_finish
    return heard, early


def spy_chunks(eng):
    """(the device's alive-after row, the host's active after the slot
    half, whether the carry was kept) of every chunk."""
    log = []
    take = eng._take_chunk

    def spy(pulled):
        agreed = take(pulled)
        log.append((pulled[-1] != 0, eng.active.copy(), agreed))
        return agreed

    eng._take_chunk = spy
    return log


@pytest.mark.parametrize("chunk", (3, 4, 8))
def test_deferred_equals_serial_order(model, chunk):
    """(a), (c), (g): same tokens, reasons, per-request on_token sequence
    and final rng as the serial order; finished only after the last token
    was handed over; the device ends exactly the rows the host ends."""
    cfg, params = model
    runs = {}
    for cls in (SerialEngine, InferenceEngine):
        eng = cls(cfg, params, max_slots=4, decode_chunk=chunk, seed=7)
        reqs = mixed_batch(cfg, params)
        heard, early = hear(reqs)
        chunks = spy_chunks(eng) if cls is InferenceEngine else None
        eng.generate(reqs)
        assert all(r.finished for r in reqs)
        assert not early, "finished read true before the last on_token"
        assert not eng._parked
        runs[cls] = (eng, reqs, heard, chunks)
    serial, deferred = runs[SerialEngine], runs[InferenceEngine]
    for a, b in zip(serial[1], deferred[1]):
        assert a.output_tokens == b.output_tokens
        assert a.finish_reason == b.finish_reason
    assert [r.finish_reason for r in deferred[1]] == [
        "stop", "length", "length", "length", "length", "length", "length"]
    assert len(deferred[1][2].output_tokens) == 7       # out of room
    assert deferred[2] == serial[2]
    assert deferred[2] == [r.output_tokens for r in deferred[1]]
    assert np.array_equal(jax.random.key_data(serial[0].rng),
                          jax.random.key_data(deferred[0].rng))
    # (g) every chunk of the run, every slot.
    assert deferred[3]
    for alive, active, agreed in deferred[3]:
        assert np.array_equal(alive, active) and agreed
    eng = deferred[0]
    assert eng.decode_deliveries["deferred"] > 0
    assert serial[0].decode_deliveries["deferred"] == 0
    assert eng.operand_places["carry"] > 0 or chunk == 8
    assert serial[0].operand_places["carry"] == 0


def test_freed_slot_readmitted_next_tick(model):
    """(b): the old request's last tokens are handed over after its slot
    went to a new request, and to the old request alone."""
    cfg, params = model
    eng = InferenceEngine(cfg, params, max_slots=1, decode_chunk=4)
    old = Request(prompt_tokens=[5, 9, 17], max_tokens=5)   # 1 + one chunk
    new = Request(prompt_tokens=[3, 4, 5, 6], max_tokens=6)
    tenant_at_token = []
    heard, early = hear([old, new])
    on_old = old.on_token

    def on_token(tok):
        tenant_at_token.append((eng.slot_req[0], new._admitted > 0))
        on_old(tok)

    old.on_token = on_token
    eng.submit(old)
    eng.submit(new)
    eng.step()                    # prefill old, decode its only chunk
    assert len(old.output_tokens) == 5 and not old.finished
    assert len(heard[0]) == 1 and len(eng._parked) == 1
    assert eng.slot_req[0] is None          # the slot half freed it
    eng.step()                    # admits `new` into slot 0
    assert old.finished and old.finish_reason == "length"
    assert heard[0] == old.output_tokens
    # Its last four tokens were handed over while the prefill that gives
    # the slot to `new` ran on the device.
    assert tenant_at_token == [(old, False)] + [(None, True)] * 4
    while eng.has_work():
        eng.step()
    assert heard[1] == new.output_tokens and len(heard[1]) == 6
    assert not early and not eng._parked
    assert old.output_tokens == greedy(cfg, params, [5, 9, 17], 5)
    assert new.output_tokens == greedy(cfg, params, [3, 4, 5, 6], 6)


def test_nothing_parked_without_work_or_after_reset(model):
    """(d): has_work() false, generate() on a timeout, and reset() leave
    nothing parked."""
    cfg, params = model
    eng = InferenceEngine(cfg, params, max_slots=2, decode_chunk=4)
    a = Request(prompt_tokens=[5, 9], max_tokens=9)
    b = Request(prompt_tokens=[3, 4, 5], max_tokens=30)
    heard, _ = hear([a, b])
    eng.submit(a)
    eng.submit(b)
    eng.step()
    assert eng._parked and eng.has_work()
    while eng.has_work():
        eng.step()
    assert not eng._parked                  # the last chunk: at once
    assert heard == [a.output_tokens, b.output_tokens]
    # generate() that runs out of time hands over what was decoded.
    c = Request(prompt_tokens=[7, 7, 7], max_tokens=30)
    heard, _ = hear([c])
    eng.generate([c], timeout_s=0.0)
    eng.submit(Request(prompt_tokens=[1], max_tokens=30))
    eng.step()
    eng.step()
    assert eng._parked and heard[0] == c.output_tokens[:len(heard[0])]
    eng.reset()
    assert not eng._parked and eng._dev_blocks is None
    assert heard[0] == c.output_tokens and not c.finished


def test_worker_drain_and_crash_path_hand_over_parked_tokens(
        model, monkeypatch):
    """(d): after EngineWorker.drain nothing is parked; when a step
    raises, the chunk before it is handed over first — the request it
    finished resolves with its result, the others with the error."""
    from runbooks_tpu.serve.api import EngineWorker

    cfg, params = model
    eng = InferenceEngine(cfg, params, max_slots=2, decode_chunk=4)
    worker = EngineWorker(eng)
    try:
        reqs = [Request(prompt_tokens=[5, 9, 17], max_tokens=9),
                Request(prompt_tokens=[3, 4], max_tokens=14)]
        heard, early = hear(reqs)
        futs = worker.submit_many(reqs)
        assert worker.drain(timeout_s=60.0)
        assert not eng._parked and not eng.has_work()
        assert [f.result(timeout=5) for f in futs] == reqs
        assert heard == [r.output_tokens for r in reqs] and not early
    finally:
        worker.stop()

    # The third step() raises at its top: two chunks were decoded, the
    # second finished `done` (1 + 4 + 4 tokens) and is still parked.
    monkeypatch.setenv("RBT_FAULT_INJECT", "engine:2")
    eng = InferenceEngine(cfg, params, max_slots=2, decode_chunk=4)
    worker = EngineWorker(eng)
    try:
        done = Request(prompt_tokens=[5, 9, 17], max_tokens=9)
        doomed = Request(prompt_tokens=[3, 4], max_tokens=30)
        heard, early = hear([done, doomed])
        f_done, f_doomed = worker.submit_many([done, doomed])
        assert f_done.result(timeout=60) is done
        with pytest.raises(Exception, match="RBT_FAULT_INJECT"):
            f_doomed.result(timeout=60)
        assert done.finish_reason == "length"
        assert heard[0] == done.output_tokens and len(heard[0]) == 9
        assert heard[1] == doomed.output_tokens and len(heard[1]) == 9
        assert not early and not eng._parked
    finally:
        worker.stop()


@pytest.mark.parametrize("kind", ("grammar", "speculative"))
def test_grammar_and_speculation_deliver_inline(model, kind):
    """(e): a grammar request among the active ones, or an engine with
    speculation on, hands every chunk over before the next dispatch."""
    cfg, params = model
    tok = ByteTokenizer()
    if kind == "grammar":
        eng = InferenceEngine(cfg, params, max_slots=2, decode_chunk=4,
                              grammar="on", tokenizer=tok)
        rf = {"type": "json_schema", "json_schema": {"schema": {
            "type": "object", "properties": {"ok": {"type": "boolean"}},
            "required": ["ok"], "additionalProperties": False}}}
        reqs = [Request(prompt_tokens=[101, 109, 105], max_tokens=24,
                        eos_id=tok.eos_id, response_format=rf),
                Request(prompt_tokens=[5, 9, 17], max_tokens=12)]
    else:
        eng = InferenceEngine(cfg, params, max_slots=2, decode_chunk=4,
                              speculative="ngram", draft_tokens=2)
        reqs = [Request(prompt_tokens=[5, 6, 7, 8] * 3, max_tokens=12),
                Request(prompt_tokens=[40, 2], max_tokens=12)]
    heard, early = hear(reqs)
    for r in reqs:
        eng.submit(r)
    while eng.has_work():
        eng.step()
        assert not eng._parked          # never left for a later dispatch
    assert heard == [r.output_tokens for r in reqs] and not early
    assert eng.decode_deliveries["deferred"] == 0
    if kind == "grammar":
        assert reqs[0].finish_reason == "grammar_complete"
        assert eng.decode_deliveries["inline"] > 0
        # A chunk of which the host took one token a grammar row leaves
        # the device's carry behind: every such chunk placed its operands.
        assert eng.operand_places["carry"] < eng.operand_places["rebuilt"]
        # Once the grammar request is gone, the plain one is deferred.
        more = Request(prompt_tokens=[3, 4, 5], max_tokens=12)
        eng.generate([more])
        assert eng.decode_deliveries["deferred"] > 0


def test_deadline_hands_over_parked_tokens_first(model):
    """(f): a request that expires with tokens parked gets them, then
    finishes with "deadline"."""
    cfg, params = model
    eng = InferenceEngine(cfg, params, max_slots=2, decode_chunk=4)
    req = Request(prompt_tokens=[5, 9, 17], max_tokens=30, deadline_s=0.5)
    heard, early = hear([req])
    eng.submit(req)
    eng.step()
    assert len(req.output_tokens) == 5 and len(heard[0]) == 1
    time.sleep(0.6)
    eng.step()
    assert req.finished and req.finish_reason == "deadline"
    assert heard[0] == req.output_tokens and len(heard[0]) == 5
    assert not early and not eng._parked and eng.deadline_expired == 1


def test_operands_placed_only_after_a_slot_change(model):
    """(h): a chunk after a chunk with no slot change places nothing; one
    after an admission places the blocks once. A finish the device saw
    keeps the carry, whose rows are then what the host holds."""
    cfg, params = model
    eng = InferenceEngine(cfg, params, max_slots=4, decode_chunk=3)
    eng.submit(Request(prompt_tokens=[5, 9, 17], max_tokens=30))
    eng.submit(Request(prompt_tokens=[3, 4], max_tokens=5))
    eng.step()
    assert eng.operand_places == {"carry": 0, "rebuilt": 1}
    eng.step()      # the short request ends in this chunk, on the device
    eng.step()
    assert eng.operand_places == {"carry": 2, "rebuilt": 1}
    assert eng.active.tolist() == [True, False, False, False]
    ints = np.asarray(eng._dev_blocks[0])
    assert np.array_equal(ints[ROW_ALIVE] != 0, eng.active)
    assert ints[ROW_POS, 0] == eng.lengths[0]
    eng.submit(Request(prompt_tokens=[40, 2], max_tokens=30))
    eng.step()
    assert eng.operand_places == {"carry": 2, "rebuilt": 2}
    eng.step()
    assert eng.operand_places == {"carry": 3, "rebuilt": 2}


def test_carry_keeps_its_sharding_under_a_mesh(model):
    """Under a serving mesh the int block comes back as it went in
    (replicated), so that a chunk on the carry runs the warmed program:
    no compile under traffic, admissions and carried chunks mixed."""
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg, params = model
    sentinel = obs_device.SENTINEL
    if not sentinel.install():
        pytest.skip("jax.monitoring unavailable; sentinel cannot verify")
    mesh = make_mesh(MeshConfig(data=1, fsdp=4, tensor=2))
    eng = InferenceEngine(cfg, params, max_slots=2, decode_chunk=3,
                          mesh=mesh)
    eng.warmup()
    try:
        total, unexpected = sentinel.total, sentinel.unexpected
        a = Request(prompt_tokens=[5, 9, 17], max_tokens=14)
        b = Request(prompt_tokens=[3, 4, 5, 6], max_tokens=8)
        eng.submit(a)
        eng.step()
        eng.step()
        placed = eng._place_blocks(eng._slot_ints, eng._slot_floats)[0]
        assert eng._dev_blocks[0].sharding == placed.sharding
        assert eng._dev_blocks[0].sharding.is_fully_replicated
        eng.submit(b)
        while eng.has_work():
            eng.step()
        assert eng.operand_places["carry"] >= 3
        assert eng.operand_places["rebuilt"] == 2
        assert sentinel.total == total, "compiled under traffic"
        assert sentinel.unexpected == unexpected
        assert a.output_tokens == greedy(cfg, params, [5, 9, 17], 14)
        assert b.output_tokens == greedy(cfg, params, [3, 4, 5, 6], 8)
    finally:
        eng.release_steady()


def aligned(shape, dtype, fill):
    """An array whose memory starts on a 64-byte boundary: what the CPU
    backend places without a copy."""
    n = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.zeros(n + 64, np.uint8)
    off = -raw.ctypes.data % 64
    out = raw[off:off + n].view(dtype).reshape(shape)
    out[:] = fill
    return out


@pytest.mark.parametrize("meshed", (False, True))
def test_placed_blocks_do_not_share_the_host_mirror(model, meshed):
    """The host mirror is written in place right after a chunk's pull,
    while a device that lags (a CPU mesh under load) may still read its
    operands: a placed block that shared the mirror's memory would hand
    that device a carry the others do not have."""
    from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg, params = model
    mesh = make_mesh(MeshConfig(data=1, fsdp=4, tensor=2)) if meshed else None
    eng = InferenceEngine(cfg, params, max_slots=2, mesh=mesh)
    ints, floats = aligned((7, 2), np.int32, 3), aligned((2, 2), np.float32, 3)
    placed = eng._place_blocks(ints, floats)
    ints += 1
    floats += 1
    for block in placed:
        for shard in block.addressable_shards:
            assert (np.asarray(shard.data) == 3).all()


@pytest.mark.parametrize("chunk", (4, 8))
def test_paged_release_extent_from_the_slot_half(model, chunk):
    """(i): the paged engine's finish hook runs in the slot half, after
    the chunk's tokens were appended: it releases prompt + outputs - 1
    written tokens, as the serial order does, and the next request with
    the same history shares those pages."""
    cfg, params = model
    eng = PagedInferenceEngine(cfg, params, max_slots=2, page_size=8,
                               decode_chunk=chunk)
    released = []
    release = eng.pager.release

    def spy(slot, written_tokens=None, ns=None):
        released.append(list(written_tokens))
        return release(slot, written_tokens=written_tokens, ns=ns)

    eng.pager.release = spy
    prompt = list(range(1, 12))
    req = Request(prompt_tokens=prompt, max_tokens=chunk + 3)
    other = Request(prompt_tokens=[40, 2], max_tokens=30)
    heard, early = hear([req, other])
    eng.submit(req)
    eng.submit(other)
    while not released:
        eng.step()
    # Mid-chunk finish, seen by the slot half; the delivery still waits.
    assert len(req.output_tokens) == chunk + 3 and not req.finished
    assert released == [(prompt + req.output_tokens)[:-1]]
    assert eng._parked
    while eng.has_work():
        eng.step()
    assert heard == [req.output_tokens, other.output_tokens] and not early
    again = Request(prompt_tokens=(prompt + req.output_tokens)[:18],
                    max_tokens=2)
    before = eng.prefix_tokens_reused
    eng.generate([again])
    assert eng.prefix_tokens_reused - before == 16      # two full pages
    dense = InferenceEngine(cfg, params, max_slots=2, decode_chunk=chunk)
    want = Request(prompt_tokens=prompt, max_tokens=chunk + 3)
    dense.generate([want])
    assert req.output_tokens == want.output_tokens


def test_counters_on_metrics_and_streams_complete(model):
    """Over HTTP: two overlapping streamed completions and a plain one get
    every token (the client's count is the server's), and /metrics carries
    both counter families with their labels."""
    import asyncio
    import json

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg, params = model
    app = create_server(cfg, params, ByteTokenizer(), max_slots=2,
                        decode_chunk=4, warmup=False)

    async def stream(client, prompt, n):
        r = await client.post("/v1/completions", json={
            "prompt": prompt, "max_tokens": n, "temperature": 0.0,
            "stream": True})
        assert r.status == 200
        lines = [ln async for ln in r.content]
        assert lines[-2].strip() == b"data: [DONE]" or \
            lines[-1].strip() == b"data: [DONE]"
        chunks = [json.loads(ln[6:]) for ln in lines
                  if ln.startswith(b"data: {")]
        return chunks[-1]["choices"][0]["finish_reason"]

    async def drive():
        async with TestClient(TestServer(app)) as client:
            reasons = await asyncio.gather(stream(client, "hello", 14),
                                           stream(client, "bye", 22))
            assert reasons == ["length", "length"]
            r = await client.post("/v1/completions", json={
                "prompt": "xyz", "max_tokens": 9, "temperature": 0.0})
            assert (await r.json())["usage"]["completion_tokens"] == 9
            return await (await client.get("/metrics")).text()

    text = asyncio.run(drive())
    app["worker"].stop()
    values = {ln.rsplit(" ", 1)[0]: float(ln.rsplit(" ", 1)[1])
              for ln in text.splitlines() if ln.startswith("serve_")}
    assert values["serve_tokens_generated_total"] == 14 + 22 + 9
    deferred = values['serve_decode_chunks_total{delivery="deferred"}']
    inline = values['serve_decode_chunks_total{delivery="inline"}']
    carry = values['serve_decode_operand_places_total{kind="carry"}']
    rebuilt = values['serve_decode_operand_places_total{kind="rebuilt"}']
    assert deferred > inline >= 1 and carry > rebuilt >= 1
    assert deferred + inline == carry + rebuilt
