"""ServeOptions (runbooks_tpu/api/serve_params.py): the one declaration of
the serving options. The controller's validate_params, serve/api.main and
both engines build this record; these tests hold the three of them, and
docs/api.md, to it.

The REFUSED table's texts are the ones the controller's hand-written
validate_params produced before the record existed (commit d5c86cb): a
spec it refused is still refused with the same words."""

import dataclasses
import os
import subprocess
import sys

import pytest

from runbooks_tpu.api.serve_params import (
    OptionError,
    ServeOptions,
    spellings,
)
from runbooks_tpu.controller.common import validate_params

FIELDS = {f.name: f for f in dataclasses.fields(ServeOptions)}

# One sound, non-default value per field (kv_host_pages and preemption
# need the paged engine beside them).
VALUES = {
    "max_slots": 4, "max_seq_len": 256, "warmup": False,
    "warm_prefix": True, "auto_prefix_chat": True, "prefix_cache_size": 6,
    "prefill_budget": 128, "decode_chunk": 4, "max_queue": 32,
    "request_timeout_s": 2.5, "drain_timeout_s": 5.0, "quantize_kv": True,
    "kv_paging": "paged", "page_size": 32, "num_pages": 64,
    "kv_host_pages": 8, "preemption": "swap",
    "queue_share_interactive": 0.5, "queue_share_standard": 0.75,
    "queue_share_batch": 0.25, "speculative": "ngram", "draft_tokens": 6,
    "ngram_max": 4, "ngram_min": 2, "adapter_pool": 3, "lora_rank": 16,
    "lora_targets": ("attn.wq", "attn.wv"), "adapter_dir": "/srv/adapters",
    "grammar": "on", "grammar_cache_size": 16,
}
BESIDE = {"kv_host_pages": {"kv_paging": "paged"},
          "preemption": {"kv_paging": "paged"},
          "lora_rank": {"adapter_pool": 2},
          "adapter_dir": {"adapter_pool": 2},
          "grammar_cache_size": {"grammar": "on"}}


def test_every_field_has_a_value_here():
    assert set(VALUES) == set(FIELDS)
    for name, value in VALUES.items():
        assert value != FIELDS[name].default, name


@pytest.mark.parametrize("name", sorted(FIELDS))
def test_key_parses_the_same_under_every_spelling(name):
    beside = BESIDE.get(name, {})
    want = ServeOptions(**{name: VALUES[name], **beside})
    assert getattr(want, name) == VALUES[name]
    assert 1 <= len(spellings(name)) <= 3
    for key in spellings(name):
        got = ServeOptions.from_params({key: VALUES[name], **beside})
        assert got == want, key
        assert validate_params({key: VALUES[name], **beside}) is None, key


@pytest.mark.parametrize(
    "name", sorted(n for n in FIELDS if n != "lora_targets"))
def test_key_parses_as_a_quoted_string(name):
    # YAML specs quote freely; PARAM_* env values that are not JSON stay
    # strings. (lora_targets is a list: nothing to quote.)
    beside = BESIDE.get(name, {})
    value = VALUES[name]
    quoted = str(value).lower() if isinstance(value, bool) else str(value)
    got = ServeOptions.from_params({name: quoted, **beside})
    assert got == ServeOptions(**{name: value, **beside})
    assert type(getattr(got, name)) is type(value)


def test_spellings():
    assert spellings("kv_host_pages") == (
        "kv_host_pages", "kvHostPages", "kvhostpages")
    assert spellings("queue_share_batch") == (
        "queue_share_batch", "queueShareBatch", "queuesharebatch")
    assert spellings("preemption") == ("preemption",)
    # the first spelling present wins, as serve/api._param_any had it
    assert ServeOptions.from_params(
        {"ngramMax": 5, "ngram_max": 4}).ngram_max == 4


def test_null_and_unknown_keys_are_not_the_records_business():
    assert ServeOptions.from_params(
        {"max_queue": None, "model": "falcon-7b", "mesh_tensor": 4,
         "batch_size": 8, "port": 8081}) == ServeOptions()
    # ...but a keyword nobody declared is refused by name
    with pytest.raises(TypeError, match="max_slotz"):
        ServeOptions(max_slotz=4)
    with pytest.raises(OptionError, match="None is not a value"):
        ServeOptions(max_slots=None)
    assert ServeOptions(max_seq_len=None).max_seq_len is None


def test_booleans_yaml_makes_of_on_and_off():
    # Unquoted `grammar: on` / `kv_paging: off` reach the controller as
    # YAML 1.1 booleans, and Python callers say kv_paging=True.
    assert ServeOptions.from_params({"grammar": True}).grammar == "on"
    assert ServeOptions.from_params({"kv_paging": False}).kv_paging == "off"
    assert ServeOptions(kv_paging=True).kv_paging == "paged"
    assert ServeOptions.from_params({"warmup": "false"}).warmup is False
    assert ServeOptions.from_params({"warmup": 0}).warmup is False
    with pytest.raises(OptionError, match="warmup: 'nope' is not a bool"):
        ServeOptions.from_params({"warmup": "nope"})


# spec -> the text the parent's controller refused it with.
_POOL = ("only applies to a pooled engine; set adapter_pool >= 1 "
         "(docs/multi-tenant-lora.md)")
_COMBINE = (
    "spec.params.adapter: cannot combine with adapter_pool on one Server "
    "— the load-time fold serves ONE tenant, the pool serves per-request "
    "adapters; point tenant Servers at this pool via spec.engineRef "
    "instead (docs/multi-tenant-lora.md)")
_HOST = ("spec.params.kv_host_pages: the host KV tier swaps radix PAGES; "
         "set kv_paging: paged (docs/paged-kv.md)")
_SWAP = ("spec.params.preemption: swap preempts at page granularity; set "
         "kv_paging: paged (docs/paged-kv.md)")
_GRAMMAR = "only applies with grammar: on (docs/structured-output.md)"
REFUSED = [
    # tests/test_speculative.py
    ({"speculative": "medusa"},
     "spec.params.speculative: 'medusa' is not one of off|ngram"),
    ({"draft_tokens": 0}, "spec.params.draft_tokens: 0 must be >= 1"),
    ({"draftTokens": "four"},
     "spec.params.draftTokens: 'four' is not an integer"),
    ({"drafttokens": 0}, "spec.params.drafttokens: 0 must be >= 1"),
    ({"ngram_min": 3, "ngram_max": 2},
     "spec.params.ngram_min: 3 must be <= ngram_max 2"),
    ({"ngram_min": 5}, "spec.params.ngram_min: 5 must be <= ngram_max 3"),
    ({"ngramMin": 0}, "spec.params.ngramMin: 0 must be >= 1"),
    ({"ngramMax": 2, "ngramMin": 3},
     "spec.params.ngram_min: 3 must be <= ngram_max 2"),
    ({"ngram_max": "x"}, "spec.params.ngram_max: 'x' is not an integer"),
    # tests/test_lora_serving.py
    ({"adapter_pool": -1}, "spec.params.adapter_pool: -1 must be >= 0"),
    ({"adapter_pool": 2, "lora_rank": 0},
     "spec.params.lora_rank: 0 must be >= 1"),
    ({"lora_rank": 8}, f"spec.params.lora_rank: {_POOL}"),
    ({"loraRank": 8}, f"spec.params.loraRank: {_POOL}"),
    ({"adapter_dir": "/srv/a"}, f"spec.params.adapter_dir: {_POOL}"),
    ({"adapterdir": "/srv/a"}, f"spec.params.adapterdir: {_POOL}"),
    ({"adapter": "  "},
     "spec.params.adapter: '  ' must be a non-empty path"),
    ({"adapter": 3}, "spec.params.adapter: 3 must be a non-empty path"),
    ({"adapter": "tenants/a", "adapter_pool": 4}, _COMBINE),
    ({"adapter": "tenants/a", "adapterPool": "4"}, _COMBINE),
    # tests/test_paging.py
    ({"kv_paging": "pagedd"},
     "spec.params.kv_paging: 'pagedd' is not one of off|paged"),
    ({"kvPaging": "on"},
     "spec.params.kvPaging: 'on' is not one of off|paged"),
    ({"page_size": 0}, "spec.params.page_size: 0 must be >= 8"),
    ({"pageSize": 4}, "spec.params.pageSize: 4 must be >= 8"),
    ({"num_pages": "many"},
     "spec.params.num_pages: 'many' is not an integer"),
    ({"numpages": 0}, "spec.params.numpages: 0 must be >= 1"),
    # tests/test_kv_tier.py
    ({"kv_paging": "paged", "preemption": "swa"},
     "spec.params.preemption: 'swa' is not one of off|swap"),
    ({"kv_paging": "paged", "kv_host_pages": -1},
     "spec.params.kv_host_pages: -1 must be >= 0"),
    ({"kv_paging": "paged", "kv_host_pages": "many"},
     "spec.params.kv_host_pages: 'many' is not an integer"),
    ({"queue_share_batch": 0},
     "spec.params.queue_share_batch: 0 must be in (0, 1]"),
    ({"queueShareInteractive": 1.5},
     "spec.params.queueShareInteractive: 1.5 must be in (0, 1]"),
    ({"queuesharestandard": "half"},
     "spec.params.queuesharestandard: 'half' is not a number"),
    ({"kv_host_pages": 4}, _HOST),
    ({"kvHostPages": 4}, _HOST),
    ({"preemption": "swap"}, _SWAP),
    ({"kv_paging": "off", "preemption": "swap"}, _SWAP),
    # tests/test_grammar.py
    ({"grammar": "maybe"},
     "spec.params.grammar: 'maybe' is not one of off|on"),
    ({"grammar": "on", "grammar_cache_size": 0},
     "spec.params.grammar_cache_size: 0 must be >= 1"),
    ({"grammar_cache_size": 8}, f"spec.params.grammar_cache_size: {_GRAMMAR}"),
    ({"grammar": "off", "grammarCacheSize": 8},
     f"spec.params.grammarCacheSize: {_GRAMMAR}"),
    # tests/test_controllers.py, tests/test_fault_tolerance.py
    ({"max_queue": -1}, "spec.params.max_queue: -1 must be >= 0"),
    ({"max_queue": "lots"},
     "spec.params.max_queue: 'lots' is not an integer"),
    ({"request_timeout_s": -1},
     "spec.params.request_timeout_s: -1 must be >= 0.0"),
    ({"request_timeout_s": "soon"},
     "spec.params.request_timeout_s: 'soon' is not a number"),
    ({"drain_timeout_s": -0.5},
     "spec.params.drain_timeout_s: -0.5 must be >= 0.0"),
]
# Specs the parent's controller accepted (its feature tests' cases, the
# benchmark's serving cells, a trainer's spec).
SOUND = [
    {}, {"speculative": "ngram"}, {"speculative": "off"},
    {"draftTokens": 8, "ngramMax": 4, "ngramMin": 2}, {"ngram_min": 3},
    {"ngram_max": 1},
    {"adapter_pool": 8, "lora_rank": 16, "adapter_dir": "/srv/adapters"},
    {"adapter": "tenants/a"}, {"adapterPool": 4},
    {"kv_paging": "paged", "page_size": 16, "num_pages": 512},
    {"kvPaging": "off"},
    {"kv_paging": "paged", "kv_host_pages": 64, "preemption": "swap",
     "queue_share_batch": 0.25},
    {"kvPaging": "paged", "kvHostPages": 8}, {"grammar": "on"},
    {"grammar": "on", "grammar_cache_size": 4}, {"max_queue": 0},
    {"max_queue": "32", "request_timeout_s": "1.5", "drain_timeout_s": 0},
    {"model": "falcon-7b", "max_slots": 8, "max_seq_len": 2048,
     "warmup": True, "max_queue": 64, "mesh_tensor": 4},
    {"batch_size": 8, "steps": 100, "lora": {"rank": 8}},
]


@pytest.mark.parametrize("spec,text", REFUSED,
                         ids=[repr(s) for s, _ in REFUSED])
def test_refused_with_the_parents_text(spec, text):
    # the controller (a condition) and the server (at start-up) agree
    assert validate_params(spec) == text
    with pytest.raises(OptionError) as err:
        ServeOptions.from_params(spec)
    assert str(err.value) == text
    assert isinstance(err.value, ValueError)


@pytest.mark.parametrize("spec", SOUND, ids=[repr(s) for s in SOUND])
def test_sound_spec_is_accepted_by_both(spec):
    assert validate_params(spec) is None
    ServeOptions.from_params(spec)


def test_keywords_refuse_like_params():
    # Engines and create_server build the record from keywords: the same
    # rules, the field's own name in the text.
    with pytest.raises(OptionError, match=r"ngram_min: 2 must be <= ngram"):
        ServeOptions(ngram_max=1, ngram_min=2)
    with pytest.raises(OptionError, match="decode_chunk: 0 must be >= 1"):
        ServeOptions(decode_chunk=0)
    with pytest.raises(OptionError, match="kv_paging: paged"):
        ServeOptions(preemption="swap")
    # what only a spec can get wrong is not an engine's error
    assert ServeOptions(lora_rank=4).lora_rank == 4


def test_record_is_frozen_and_round_trips():
    options = ServeOptions.from_params(
        {"kvPaging": "paged", "queueShareBatch": "0.25", "max_slots": "2"})
    with pytest.raises(dataclasses.FrozenInstanceError):
        options.max_slots = 3
    assert ServeOptions(**dataclasses.asdict(options)) == options
    assert options.queue_shares == {
        "interactive": 1.0, "standard": 1.0, "batch": 0.25}


def test_module_is_jax_free():
    code = ("import sys; import runbooks_tpu.api.serve_params; "
            "import runbooks_tpu.controller.common; "
            "bad = [m for m in sys.modules if m == 'jax' "
            "or m.startswith('jax.')]; "
            "assert not bad, bad")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-c", code], check=True, cwd=root,
                   env={**os.environ, "PYTHONPATH": root})


SERVING_POLICY = ("speculative", "draft_tokens", "ngram_max", "ngram_min",
                  "adapter_pool", "lora_rank", "lora_targets", "quantize_kv")


@pytest.mark.parametrize("name", SERVING_POLICY)
def test_model_config_holds_no_serving_policy(name):
    from runbooks_tpu.models.config import ModelConfig, get_config

    assert name not in {f.name for f in dataclasses.fields(ModelConfig)}
    with pytest.raises(TypeError, match=name):
        get_config("debug", **{name: VALUES[name]})
    assert name in FIELDS


def test_controller_declares_no_serving_option():
    import runbooks_tpu.controller.common as common

    tables = {**common.ENUM_PARAMS, **common.INT_PARAMS,
              **common.FLOAT_PARAMS}
    known = {key for name in FIELDS for key in spellings(name)}
    assert not known & set(tables)
    src = open(common.__file__).read()
    for name in FIELDS:
        for key in spellings(name):
            assert f'"{key}"' not in src, key


def test_docs_table_is_the_record():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    doc = open(os.path.join(root, "docs", "api.md")).read()
    assert ServeOptions.table() in doc
    # ...and no other table there gives a server option a row of its own
    rest = doc.replace(ServeOptions.table(), "")
    for name in FIELDS:
        assert f"| `{name}`" not in rest, name


def test_load_model_leaves_quantize_kv_to_the_engine():
    from runbooks_tpu.serve.api import load_model
    from runbooks_tpu.serve.engine import InferenceEngine

    spec = {"model": "debug", "quantize_kv": True}
    cfg, weights = load_model(spec)
    assert not hasattr(cfg, "quantize_kv")
    options = ServeOptions.from_params(spec)
    on = InferenceEngine(cfg, weights, max_slots=1, max_seq_len=32,
                         quantize_kv=options.quantize_kv)
    off = InferenceEngine(cfg, weights, max_slots=1, max_seq_len=32)
    assert on.quantize_kv and on.cache.k_scale is not None
    assert not off.quantize_kv and off.cache.k_scale is None
