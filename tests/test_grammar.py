"""Grammar-constrained structured output tests (docs/structured-output.md).

Core invariants, in roughly the order they are built:

- compiler: the JSON-schema/EBNF front-ends accept exactly their
  language, refuse unsupported constructs by path, and the token DFA's
  cursor walks valid serializations to a terminal state;
- engine: under the gmask operand a temp>0 slot can NEVER emit a
  grammar-illegal token (randomized-schema property test), an all-allow
  mask is token-identical to the unmasked engine (greedy parity), and
  constrained slots ride speculative verify unchanged (on/off parity,
  dense AND paged);
- lifecycle: preempt/swap-resume carries the DFA cursor loss-free (the
  PR-16 loss-free-resume discipline), and a steady mixed loop of
  constrained + unconstrained + LoRA traffic performs ZERO XLA compiles
  (masked program variants replace the plain set, never multiply it);
- surface: response_format end-to-end over HTTP with typed 400s
  (unsupported constructs, unknown top-level body fields), the gateway
  forwarding the field verbatim, and controller spec validation.
"""

import dataclasses
import json
import os
import random

import jax
import numpy as np
import pytest

from runbooks_tpu.models.config import get_config
from runbooks_tpu.models.transformer import init_params
from runbooks_tpu.serve.engine import InferenceEngine, Request
from runbooks_tpu.serve.grammar import (
    GrammarCache,
    GrammarError,
    TokenVocab,
    ebnf_to_ast,
    response_format_ast,
    schema_to_ast,
)
from runbooks_tpu.serve.paging import PagedInferenceEngine
from runbooks_tpu.serve.speculative import legal_draft_prefix
from runbooks_tpu.train.data import ByteTokenizer


def tiny_cfg(**over):
    # vocab_size matches the ByteTokenizer (258 = 256 bytes + bos + eos)
    # so the grammar mask width covers the tokenizer's eos id.
    base = dict(vocab_size=258, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                max_seq_len=64, dtype="float32")
    base.update(over)
    return dataclasses.replace(get_config("llama2-7b"), **base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg, jax.random.key(0))


@pytest.fixture(scope="module")
def bank(model):
    """Lazily built, module-shared engines. Engine construction is
    cheap but the first dispatch compiles the program set — sharing
    instances across tests keeps the suite inside the tier-1 wall
    budget. Only stateless-use tests draw from the bank; tests that
    assert engine counters or sentinel state build their own."""
    cfg, params = model
    engines = {}

    def get(kind, grammar=False, spec=False):
        key = (kind, grammar, spec)
        if key not in engines:
            kw = dict(max_slots=2)
            if kind == "paged":
                kw["page_size"] = 16
            if grammar:
                kw.update(grammar="on", tokenizer=TOK)
            if spec:
                kw.update(speculative="ngram", draft_tokens=4)
            cls = PagedInferenceEngine if kind == "paged" \
                else InferenceEngine
            engines[key] = cls(cfg, params, **kw)
        return engines[key]

    return get


TOK = ByteTokenizer()
VOCAB = TokenVocab.from_tokenizer(TOK)


def _cache(capacity=8):
    return GrammarCache(VOCAB, 258, capacity=capacity)


def _prompt(text=b"emit json: "):
    return [int(b) for b in text]


def _text(req):
    return bytes(t for t in req.output_tokens if t < 256).decode()


SCHEMA_RF = {"type": "json_schema", "json_schema": {"schema": {
    "type": "object",
    "properties": {"ok": {"type": "boolean"},
                   "mode": {"enum": ["a", "b"]}},
    "required": ["ok", "mode"],
    "additionalProperties": False,
}}}


# ---------------------------------------------------------------------------
# Compiler: vocab fingerprint, schema/EBNF front-ends, DFA cursor
# ---------------------------------------------------------------------------

def test_token_vocab_fingerprint_stable():
    # Content hash, not object identity: two tokenizer instances with
    # the same vocab must key the same cache entries.
    a = TokenVocab.from_tokenizer(ByteTokenizer())
    b = TokenVocab.from_tokenizer(ByteTokenizer())
    assert a.fingerprint == b.fingerprint == VOCAB.fingerprint
    assert len(a.fingerprint) == 64          # sha256 hex


def test_cursor_walks_valid_json_to_terminal():
    dfa = _cache().get(SCHEMA_RF)
    cur = dfa.cursor()
    for b in b'{"ok":true,"mode":"a"}':
        assert cur.legal(b), chr(b)
        assert cur.advance(b)
    assert cur.accepting and cur.at_terminal
    # terminal = nothing but EOS: the mask row allows exactly eos.
    row = cur.mask_row()
    assert row[VOCAB.eos_id]
    assert int(row.sum()) == 1
    # an illegal byte neither validates nor mutates
    cur2 = dfa.cursor()
    assert not cur2.legal(ord("x"))
    state_before = cur2.state
    assert not cur2.advance(ord("x"))
    assert cur2.state == state_before


def test_schema_unsupported_constructs_raise_with_path():
    cases = [
        ({"type": "object", "properties": {"a": {"$ref": "#/x"}},
          "required": ["a"], "additionalProperties": False},
         "$.a"),
        ({"oneOf": [{"type": "null"}]}, "oneOf"),
        ({"type": "string", "pattern": "a+"}, "pattern"),
        ({"type": "object", "properties": {"a": {"type": "null"}},
          "additionalProperties": True}, "additionalProperties"),
        ({"type": "object", "properties": {"a": {"type": "null"}},
          "required": [], "additionalProperties": False}, "required"),
        ({"type": "array", "items": {"type": "null"}, "minItems": 2},
         "minItems"),
        ({"type": ["string", "null"]}, "union"),
        ({"type": "frobnicate"}, "frobnicate"),
    ]
    for schema, needle in cases:
        with pytest.raises(GrammarError, match=None) as ei:
            schema_to_ast(schema)
        assert needle in str(ei.value), (schema, str(ei.value))
    with pytest.raises(GrammarError, match="json_object"):
        response_format_ast({"type": "json_object"})
    with pytest.raises(GrammarError, match="json_schema or ebnf"):
        response_format_ast({"type": "jsonschema"})


def test_ebnf_compiles_and_recursion_rejected():
    rf = {"type": "ebnf", "grammar": (
        '# toy signed integer\n'
        'root ::= sign? digit digit*\n'
        'sign ::= "-"\n'
        'digit ::= [0-9]\n')}
    dfa = _cache().get(rf)
    cur = dfa.cursor()
    for b in b"-42":
        assert cur.advance(b)
    assert cur.accepting
    assert not dfa.cursor().legal(ord("a"))
    with pytest.raises(GrammarError, match="recursive"):
        ebnf_to_ast('root ::= "(" root ")"')
    with pytest.raises(GrammarError, match="undefined"):
        ebnf_to_ast("root ::= missing")


def test_cache_lru_eviction_and_stats():
    cache = _cache(capacity=2)
    rfs = [{"type": "ebnf", "grammar": f'root ::= "{c}"'}
           for c in "abc"]
    cache.get(rfs[0])
    cache.get(rfs[0])                        # hit
    cache.get(rfs[1])
    cache.get(rfs[2])                        # evicts rfs[0]
    cache.get(rfs[0])                        # recompiles
    st = cache.stats()
    assert st["size"] == 2 and st["capacity"] == 2
    assert st["hits"] == 1 and st["misses"] == 4
    assert st["compile_seconds_total"] > 0
    assert st["tokenizer_fingerprint"] == VOCAB.fingerprint
    with pytest.raises(ValueError, match="grammar_cache_size"):
        GrammarCache(VOCAB, 258, capacity=0)


def test_legal_draft_prefix_truncates_illegal_and_terminal():
    dfa = _cache().get({"type": "ebnf", "grammar": 'root ::= "ab"'})
    cur = dfa.cursor()
    # illegal mid-draft: cut before the first token the DFA refuses
    assert legal_draft_prefix(cur, [ord("a"), ord("x")]) == [ord("a")]
    # a draft crossing the terminal accept state is cut there — the
    # slot finishes with grammar_complete and must not propose past it
    assert legal_draft_prefix(
        cur, [ord("a"), ord("b"), ord("a")]) == [ord("a"), ord("b")]
    # non-mutating: the cursor itself never advanced
    assert cur.state == dfa.cursor().state
    # unconstrained cursors pass drafts through untouched
    assert legal_draft_prefix(None, [1, 2, 3]) == [1, 2, 3]


# ---------------------------------------------------------------------------
# Engine: property test (temp>0 never illegal), parity, spec decode
# ---------------------------------------------------------------------------

def _random_schema(rng, depth=0):
    """Random schema from the supported subset. Leaves are finite
    (boolean/null/enum/const/integer) so the language is decidable per
    token; containers recurse with shrinking probability."""
    leaves = [
        {"type": "boolean"},
        {"type": "null"},
        {"type": "integer"},
        {"enum": [rng.choice(["x", "y", 1, True])]},
        {"const": rng.choice([0, "k", False, None])},
    ]
    if depth >= 2 or rng.random() < 0.4:
        return rng.choice(leaves)
    if rng.random() < 0.5:
        props = {f"p{i}": _random_schema(rng, depth + 1)
                 for i in range(rng.randint(1, 3))}
        return {"type": "object", "properties": props,
                "required": sorted(props), "additionalProperties": False}
    return {"type": "array", "items": _random_schema(rng, depth + 1),
            "minItems": rng.randint(0, 1)}


def test_random_schemas_temp_sampling_never_illegal(bank):
    """Property test: under the gmask operand, a temp>0 constrained slot
    never emits a token its DFA state forbids — verified by replaying
    every output through a fresh cursor. Completed slots parse as JSON
    the schema accepts structurally."""
    engine = bank("dense", grammar=True)
    cache = _cache(capacity=32)
    rng = random.Random(0)
    reqs = []
    for i in range(8):
        rf = {"type": "json_schema",
              "json_schema": {"schema": _random_schema(rng)}}
        reqs.append(Request(
            prompt_tokens=_prompt(), max_tokens=48,
            temperature=1.5, eos_id=TOK.eos_id, response_format=rf))
    engine.generate(reqs)
    for r in reqs:
        assert r.finish_reason != "error"
        cur = cache.cursor(r.response_format)
        for t in r.output_tokens:
            if t == TOK.eos_id:
                assert cur.accepting     # EOS only at accept states
                break
            assert cur.advance(t), (r.response_format, _text(r), t)
        if r.finish_reason == "grammar_complete":
            json.loads(_text(r))         # 100% parse on completion


def test_full_parse_rate_bounded_schemas(bank):
    """Finite-language schemas (no stars) must complete and parse 100%
    of the time — the bench gate's assertion, test-sized."""
    engine = bank("dense", grammar=True)
    rf = {"type": "json_schema", "json_schema": {"schema": {
        "type": "object",
        "properties": {"a": {"type": "boolean"},
                       "b": {"enum": ["u", "v", "w"]},
                       "c": {"type": "null"}},
        "required": ["a", "b", "c"], "additionalProperties": False}}}
    reqs = [Request(prompt_tokens=_prompt(), max_tokens=48,
                    temperature=t, eos_id=TOK.eos_id, response_format=rf)
            for t in (0.0, 0.7, 1.0, 1.5)]
    engine.generate(reqs)
    for r in reqs:
        assert r.finish_reason == "grammar_complete"
        out = json.loads(_text(r))
        assert set(out) == {"a", "b", "c"}
        assert isinstance(out["a"], bool)
        assert out["b"] in ("u", "v", "w") and out["c"] is None


@pytest.mark.parametrize("engine_cls", ["dense", "paged"])
def test_greedy_all_allow_mask_parity(bank, engine_cls):
    """A grammar-on engine serving UNCONSTRAINED requests dispatches
    all-allow mask rows — `where(True, logits, -inf)` is the identity,
    so greedy output is token-identical to the grammar-off engine."""
    plain = bank(engine_cls)
    masked = bank(engine_cls, grammar=True)
    prompts = [_prompt(b"hello"), _prompt(b"abc def")]
    for prompt in prompts:
        a = Request(prompt_tokens=list(prompt), max_tokens=8,
                    temperature=0.0, eos_id=TOK.eos_id)
        b = Request(prompt_tokens=list(prompt), max_tokens=8,
                    temperature=0.0, eos_id=TOK.eos_id)
        plain.generate([a])
        masked.generate([b])
        assert a.output_tokens == b.output_tokens
        assert a.finish_reason == b.finish_reason


@pytest.mark.parametrize("engine_cls", ["dense", "paged"])
def test_spec_decode_parity_constrained(bank, engine_cls):
    """Constrained greedy output is token-identical with speculation on
    or off: drafts are pre-truncated to legal prefixes so the verify
    math never sees a zero-mass token, and per-position masks replay
    the same DFA states the sequential path visits."""
    base = bank(engine_cls, grammar=True)
    spec = bank(engine_cls, grammar=True, spec=True)
    for rf in (SCHEMA_RF,
               {"type": "ebnf",
                "grammar": 'root ::= "[" [0-9] ("," [0-9])* "]"'}):
        a = Request(prompt_tokens=_prompt(), max_tokens=24,
                    temperature=0.0, eos_id=TOK.eos_id,
                    response_format=rf)
        b = Request(prompt_tokens=_prompt(), max_tokens=24,
                    temperature=0.0, eos_id=TOK.eos_id,
                    response_format=rf)
        base.generate([a])
        spec.generate([b])
        assert a.output_tokens == b.output_tokens
        assert a.finish_reason == b.finish_reason


# ---------------------------------------------------------------------------
# Lifecycle: preempt/swap-resume carries the cursor, zero compiles
# ---------------------------------------------------------------------------

def test_preemption_resumes_grammar_cursor_loss_free(model):
    """Swap preemption requeues the Request object — its DFA cursor
    rides along, so the resumed constrained decode continues from the
    exact grammar state and the final output is token-identical to an
    undisturbed run (the loss-free-resume discipline, grammar
    edition)."""
    cfg, params = model
    # Fixed-literal properties => a long deterministic constrained
    # rollout (42 tokens) that stays mid-flight across several decode
    # steps yet fits max_seq_len with the prompt.
    rf = {"type": "json_schema", "json_schema": {"schema": {
        "type": "object",
        "properties": {f"k{i}": {"const": v} for i, v in
                       enumerate([True, None, "aa", False])},
        "required": [f"k{i}" for i in range(4)],
        "additionalProperties": False}}}

    def constrained(priority):
        return Request(prompt_tokens=_prompt(), max_tokens=50,
                       temperature=0.0, eos_id=TOK.eos_id,
                       response_format=rf, priority=priority)

    oracle = constrained("batch")
    undisturbed = PagedInferenceEngine(
        cfg, params, max_slots=1, page_size=16, num_pages=5,
        kv_host_pages=8, preemption="swap", decode_chunk=2,
        grammar="on", tokenizer=TOK)
    undisturbed.generate([oracle])
    assert oracle.finish_reason == "grammar_complete"
    json.loads(_text(oracle))

    engine = PagedInferenceEngine(
        cfg, params, max_slots=1, page_size=16, num_pages=5,
        kv_host_pages=8, preemption="swap", decode_chunk=2,
        grammar="on", tokenizer=TOK)
    batch = constrained("batch")
    engine.submit(batch)
    for _ in range(3):
        engine.step()
    assert engine.active.any() and not batch.finished
    inter = Request(prompt_tokens=_prompt(b"quick"), max_tokens=4,
                    temperature=0.0, eos_id=TOK.eos_id,
                    priority="interactive")
    engine.submit(inter)
    engine.step()
    assert engine.preemptions == 1 and not batch.finished
    while engine.has_work():
        engine.step()
    assert engine.preempted_resumed == 1
    assert batch.output_tokens == oracle.output_tokens
    assert batch.finish_reason == "grammar_complete"


def test_zero_unexpected_compiles_mixed_grammar_lora_loop(
        model, tmp_path):
    """Warmed grammar-on pooled engine: a steady loop mixing
    constrained, unconstrained, and LoRA-adapter requests performs ZERO
    XLA compiles — the gmask operand rides every dispatch (all-allow
    rows for unconstrained lanes) so masked program variants replace
    the plain set instead of multiplying the census.

    Dense engine only: a full paged warmup costs ~30 s of compiles on
    CPU and the paged grammar dispatch is already covered by the parity
    and preemption tests here plus the RBT_BENCH_GRAMMAR bench axis;
    the mixed-traffic zero-compile property itself is engine-agnostic."""
    engine_cls = "dense"
    from runbooks_tpu.obs import device as obs_device
    from runbooks_tpu.serve.lora_pool import save_adapter
    from runbooks_tpu.train.lora import LoraConfig, init_lora

    cfg, params = model
    lora = init_lora(params, LoraConfig(rank=4, alpha=8.0),
                     jax.random.key(11))
    lora = jax.tree.map(
        lambda x: x + 0.03 * jax.random.normal(
            jax.random.key(21), x.shape, x.dtype), lora)
    path = os.path.join(str(tmp_path), "tenant0")
    save_adapter(path, lora, rank=4, alpha=8.0)

    if engine_cls == "paged":
        eng = PagedInferenceEngine(cfg, params, max_slots=2, page_size=16,
                                   adapter_pool=2, lora_rank=8,
                                   grammar="on", tokenizer=TOK)
    else:
        eng = InferenceEngine(cfg, params, max_slots=2, adapter_pool=2,
                              lora_rank=8, grammar="on", tokenizer=TOK)
    sentinel = obs_device.SENTINEL
    if not sentinel.install():
        pytest.skip("jax.monitoring unavailable; sentinel cannot verify")
    eng.warmup()
    census = eng.warmup_census
    assert census["grammar"] == "on"
    assert census["grammar_cache_size"] == 64
    before_total = sentinel.total
    before_unexpected = sentinel.unexpected
    try:
        for i in range(6):
            r = Request(
                prompt_tokens=_prompt(), max_tokens=6, temperature=0.0,
                eos_id=TOK.eos_id,
                response_format=SCHEMA_RF if i % 3 == 0 else None,
                adapter=path if i % 3 == 1 else None)
            eng.generate([r])
            assert r.finished and r.finish_reason != "error"
        stats = eng.grammar_stats()
        assert stats["requests_total"] == 2      # the loop really mixed
        assert stats["hits"] >= 1                # ...and the cache hit
        assert sentinel.total == before_total, "compiled under traffic"
        assert sentinel.unexpected == before_unexpected
    finally:
        eng.release_steady()


# ---------------------------------------------------------------------------
# Engine/controller validation
# ---------------------------------------------------------------------------

def test_engine_grammar_validation(model):
    cfg, params = model
    with pytest.raises(ValueError, match="grammar"):
        InferenceEngine(cfg, params, max_slots=1, grammar="maybe")
    with pytest.raises(ValueError, match="tokenizer"):
        InferenceEngine(cfg, params, max_slots=1, grammar="on")
    off = InferenceEngine(cfg, params, max_slots=1)
    with pytest.raises(ValueError, match="grammar: on"):
        off.submit(Request(prompt_tokens=[1, 2],
                           response_format=SCHEMA_RF))
    on = InferenceEngine(cfg, params, max_slots=1, grammar="on",
                         tokenizer=TOK)
    with pytest.raises(ValueError, match="unsupported schema construct"):
        on.submit(Request(prompt_tokens=[1, 2], response_format={
            "type": "json_schema",
            "json_schema": {"schema": {"oneOf": []}}}))
    assert on.tokenizer_fingerprint == VOCAB.fingerprint


def test_validate_params_grammar():
    from runbooks_tpu.controller.common import validate_params

    assert validate_params({"grammar": "on"}) is None
    assert validate_params({"grammar": "on",
                            "grammar_cache_size": 4}) is None
    assert "grammar" in validate_params({"grammar": "maybe"})
    assert ">= 1" in validate_params({"grammar": "on",
                                      "grammar_cache_size": 0})
    # cache knob without the mode is a spec typo, not a silent no-op
    err = validate_params({"grammar_cache_size": 8})
    assert err is not None and "grammar: on" in err
    err = validate_params({"grammar": "off", "grammarCacheSize": 8})
    assert err is not None and "grammar: on" in err


# ---------------------------------------------------------------------------
# HTTP surface + gateway forwarding
# ---------------------------------------------------------------------------

def test_http_response_format_end_to_end(model):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg, params = model
    app = create_server(cfg, params, tokenizer=ByteTokenizer(),
                        max_slots=2, grammar="on", warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "emit json: ", "max_tokens": 32,
                "temperature": 0.0, "response_format": SCHEMA_RF})
            assert r.status == 200
            body = await r.json()
            choice = body["choices"][0]
            assert choice["finish_reason"] == "grammar_complete"
            out = json.loads(choice["text"])
            assert set(out) == {"ok", "mode"}

            # unsupported construct -> typed 400 naming the path
            r = await client.post("/v1/completions", json={
                "prompt": "x", "response_format": {
                    "type": "json_schema", "json_schema": {"schema": {
                        "type": "string", "pattern": "a+"}}}})
            assert r.status == 400
            assert "pattern" in (await r.json())["error"]["message"]

            # non-object response_format -> 400 before admission
            r = await client.post("/v1/completions", json={
                "prompt": "x", "response_format": "json"})
            assert r.status == 400

            # a TYPO'D field must 400 listing the unknown names, never
            # silently serve unconstrained output
            r = await client.post("/v1/completions", json={
                "prompt": "x", "respose_format": SCHEMA_RF})
            assert r.status == 400
            err = (await r.json())["error"]
            assert err["type"] == "unknown_field"
            assert err["fields"] == ["respose_format"]
            assert "respose_format" in err["message"]

            # observability: grammar families + tokenizer fingerprint
            r = await client.get("/metrics")
            text = await r.text()
            assert "serve_grammar_requests_total" in text
            assert "serve_grammar_cache_misses_total" in text
            r = await client.get("/debug/programs")
            dbg = await r.json()
            assert dbg["tokenizer_fingerprint"] == VOCAB.fingerprint
            assert dbg["grammar"]["mode"] == "on"
            assert dbg["grammar"]["requests_total"] >= 1

    asyncio.run(drive())


def test_http_response_format_rejected_when_grammar_off(model):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.api import create_server

    cfg, params = model
    app = create_server(cfg, params, tokenizer=ByteTokenizer(),
                        max_slots=1, warmup=False)

    async def drive():
        async with TestClient(TestServer(app)) as client:
            r = await client.post("/v1/completions", json={
                "prompt": "x", "response_format": SCHEMA_RF})
            assert r.status == 400
            msg = (await r.json())["error"]["message"]
            assert "grammar: on" in msg
            r = await client.get("/debug/programs")
            dbg = await r.json()
            assert dbg["grammar"] == {"mode": "off"}
            # fingerprint exposed even with grammar off: fleet audits
            # compare replica vocabs BEFORE enabling constrained routing
            assert dbg["tokenizer_fingerprint"] == VOCAB.fingerprint

    asyncio.run(drive())


def test_gateway_forwards_response_format():
    import asyncio

    from aiohttp import web
    from aiohttp.test_utils import TestClient, TestServer

    from runbooks_tpu.serve.gateway import create_gateway

    async def drive():
        replica = web.Application()
        replica["hits"] = []

        async def completions(request):
            body = await request.json()
            replica["hits"].append(body)
            return web.json_response({"choices": [{
                "text": '{"ok":true,"mode":"a"}',
                "finish_reason": "grammar_complete"}]})

        replica.router.add_post("/v1/completions", completions)
        srv = TestServer(replica)
        await srv.start_server()
        gw = create_gateway({"a": f"http://127.0.0.1:{srv.port}"},
                            scrape_interval_s=0)
        async with TestClient(TestServer(gw)) as client:
            resp = await client.post("/v1/completions", json={
                "prompt": "emit json: ", "max_tokens": 32,
                "response_format": SCHEMA_RF})
            assert resp.status == 200
            data = await resp.json()
            # finish_reason passes through the proxy verbatim
            assert data["choices"][0]["finish_reason"] \
                == "grammar_complete"
        # the replica saw the field byte-for-byte — the gateway forwards
        # the whole body without learning the grammar schema
        assert replica["hits"][0]["response_format"] == SCHEMA_RF
        await srv.close()

    asyncio.run(drive())
