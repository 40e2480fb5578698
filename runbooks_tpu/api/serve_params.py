"""The serving options: one record, declared once.

``ServeOptions`` is what a Server's ``spec.params`` says about HOW to serve
(slots, queue, paging, speculation, adapter pool, grammar, ...); which model
and which weights (``model``, ``checkpoint``, ``quantize``, ``adapter``,
``mesh_*``, ``tokenizer``, ``port``) are read where they are used. The
controller validates a spec by building the record
(``controller/common.validate_params``), ``serve/api.main`` builds it from
params.json, and ``create_server`` and both engines take its fields as
keywords and hold the record as ``engine.options``. Name, type, default,
floor or allowed values and the text ``docs/api.md`` prints all live on the
field; the rules between fields live in ``__post_init__``.

Imports nothing beyond the standard library: the controller stays jax-free.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

QOS_CLASSES = ("interactive", "standard", "batch")


class OptionError(ValueError):
    """A refused option. Reads ``spec.params.<key>: <detail>``, the text a
    Server's InvalidParams condition and the server's start-up error
    share; ``from_params`` puts the spec's own spelling of the key in."""

    def __init__(self, field: str, detail: str, respell: bool = True):
        super().__init__(f"spec.params.{field}: {detail}")
        self.field, self.detail, self.respell = field, detail, respell


def _opt(default, doc: str, *, floor=None, choices: Tuple[str, ...] = ()):
    return dataclasses.field(default=default, metadata={
        "doc": doc, "floor": floor, "choices": choices})


def spellings(name: str) -> Tuple[str, ...]:
    """The three spellings of a key: snake_case (params.json), camelCase
    (the reference's spec style) and the lowercase that the PARAM_* env
    round-trip makes of the camelCase one. A name of one word has one."""
    camel = re.sub(r"_([a-z0-9])", lambda m: m.group(1).upper(), name)
    return tuple(dict.fromkeys((name, camel, camel.lower())))


@dataclasses.dataclass(frozen=True)
class ServeOptions:
    max_slots: int = _opt(
        8, "concurrent sequences: rows of the KV slot pool and of every "
        "decode dispatch")
    max_seq_len: Optional[int] = _opt(
        None, "prompt + generated tokens a slot holds (default: the "
        "model's `max_seq_len`)")
    warmup: bool = _opt(
        True, "compile every reachable prefill/decode program before "
        "readiness flips, so no request waits for a compile")
    warm_prefix: bool = _opt(
        False, "with `warmup`, also pre-compile the /v1/prefix KV builder "
        "per bucket, so a runtime registration never compiles on the "
        "serving thread")
    auto_prefix_chat: bool = _opt(
        False, "each chat turn's prompt KV becomes the next turn's shared "
        "prefix (registered from the slot, zero extra forwards)")
    prefix_cache_size: Optional[int] = _opt(
        None, "registered shared prefixes kept on device, LRU (default "
        "`max(4, 2 * max_slots)`)")
    prefill_budget: Optional[int] = _opt(
        None, "prompt tokens (bucket-padded) admitted per step, so a burst "
        "of prefills cannot stall every stream's next token; one "
        "over-budget request still admits alone (default: `max_seq_len`)")
    decode_chunk: Optional[int] = _opt(
        None, "decode steps run on the device per host round-trip: larger "
        "amortizes the sync, at up to chunk-1 steps of admission latency "
        "(default 8 on TPU, 1 elsewhere)", floor=1)
    max_queue: Optional[int] = _opt(
        None, "admission-queue bound: a full queue returns HTTP 429 + "
        "Retry-After; 0 sheds everything (default `max(16, 4 * "
        "max_slots)`; docs/fault-tolerance.md)", floor=0)
    request_timeout_s: Optional[float] = _opt(
        None, "default per-request wall-clock deadline, enforced between "
        "decode chunks (body field `timeout` overrides; expiry finishes "
        "with `finish_reason: deadline`; 0 or unset = none)", floor=0.0)
    drain_timeout_s: float = _opt(
        30.0, "SIGTERM graceful-drain bound: stop admitting, finish "
        "in-flight, then exit", floor=0.0)
    quantize_kv: Optional[bool] = _opt(
        None, "int8 KV cache with per-slot-per-head f32 scales, forced on "
        "or off (default: on exactly when the weights are quantized, "
        "`quantize` != `none`; docs/quantized-serving.md)")
    kv_paging: str = _opt(
        "off", "`paged`: the KV cache is a page pool with radix-tree "
        "prefix sharing, and admission gates on free pages instead of "
        "dense slot rows (docs/paged-kv.md)", choices=("off", "paged"))
    page_size: int = _opt(
        16, "tokens per KV page; must divide `max_seq_len`", floor=8)
    num_pages: Optional[int] = _opt(
        None, "KV page-pool size (default `max_slots * max_seq_len / "
        "page_size`, the dense reservation; size down from HBM headroom "
        "to overcommit on sharing)", floor=1)
    kv_host_pages: int = _opt(
        0, "host-RAM KV swap tier in pages (needs `kv_paging: paged`): "
        "radix eviction swaps pages to pinned host buffers instead of "
        "dropping them, and a returning match swaps back in instead of "
        "re-prefilling", floor=0)
    preemption: str = _opt(
        "off", "`swap` (needs `kv_paging: paged`): under pressure the "
        "lowest-class active slot swaps out at a step boundary and "
        "re-queues with its generated tokens intact",
        choices=("off", "swap"))
    queue_share_interactive: float = _opt(
        1.0, "share of `max_queue` the `interactive` class may occupy, in "
        "(0, 1]: its queued entries are bounded by `ceil(share * "
        "max_queue)`, excess sheds 429")
    queue_share_standard: float = _opt(
        1.0, "the same for the `standard` class")
    queue_share_batch: float = _opt(
        1.0, "the same for the `batch` class")
    speculative: str = _opt(
        "off", "`ngram`: prompt-lookup speculative decoding: draft from "
        "each request's own context, verify the drafts of every slot in "
        "one batched forward; greedy outputs identical on and off "
        "(docs/speculative-decoding.md)", choices=("off", "ngram"))
    draft_tokens: Optional[int] = _opt(
        None, "speculative draft window K, a static program shape "
        "(default 4)", floor=1)
    ngram_max: int = _opt(
        3, "longest trailing n-gram the drafter matches", floor=1)
    ngram_min: int = _opt(
        1, "shortest one; `ngram_min <= ngram_max`", floor=1)
    adapter_pool: int = _opt(
        0, "multi-tenant batched LoRA: adapters resident in HBM (0 = "
        "off). A request's `adapter` pins a pool lane at admission and "
        "heterogeneous tenants batch in one dispatch "
        "(docs/multi-tenant-lora.md)", floor=0)
    lora_rank: int = _opt(
        8, "static rank bucket every pool lane pads to; an adapter of "
        "larger rank is rejected at load (pool only)", floor=1)
    lora_targets: Tuple[str, ...] = _opt(
        ("attn.wq", "attn.wk", "attn.wv", "attn.wo"),
        "weights eligible for pooled injection, dotted paths into a layer "
        "as in train/lora.py (pool only)")
    adapter_dir: Optional[str] = _opt(
        None, "root for relative per-request adapter names; absolute "
        "paths pass through (pool only)")
    grammar: str = _opt(
        "off", "`on`: a request's `response_format` (JSON-schema subset "
        "or EBNF) compiles host-side to a token DFA applied as a logit "
        "mask; constrained and unconstrained slots share one dispatch. "
        "Needs a real tokenizer; `off` answers `response_format` with a "
        "400 (docs/structured-output.md)", choices=("off", "on"))
    grammar_cache_size: int = _opt(
        64, "compiled-DFA LRU entries, keyed (grammar hash, tokenizer "
        "fingerprint); only with `grammar: on`", floor=1)

    def __post_init__(self):
        for f in dataclasses.fields(self):
            object.__setattr__(self, f.name,
                               _coerce(f, getattr(self, f.name)))
        if self.ngram_min > self.ngram_max:
            raise OptionError(
                "ngram_min", f"{self.ngram_min} must be <= ngram_max "
                f"{self.ngram_max}", respell=False)
        for cls in QOS_CLASSES:
            name = f"queue_share_{cls}"
            if not 0.0 < getattr(self, name) <= 1.0:
                raise OptionError(
                    name, f"{getattr(self, name):g} must be in (0, 1]")
        if self.kv_paging != "paged":
            if self.kv_host_pages > 0:
                raise OptionError(
                    "kv_host_pages", "the host KV tier swaps radix PAGES; "
                    "set kv_paging: paged (docs/paged-kv.md)",
                    respell=False)
            if self.preemption == "swap":
                raise OptionError(
                    "preemption", "swap preempts at page granularity; set "
                    "kv_paging: paged (docs/paged-kv.md)")

    @property
    def queue_shares(self) -> Dict[str, float]:
        return {cls: getattr(self, f"queue_share_{cls}")
                for cls in QOS_CLASSES}

    @classmethod
    def from_params(cls, params: Dict[str, Any]) -> "ServeOptions":
        """The record a params dict (a Server's spec.params, params.json
        merged with PARAM_* env) asks for. Keys it does not know are not
        its business; a null is an absent key. Raises OptionError."""
        given, spelled = {}, {}
        for f in dataclasses.fields(cls):
            key = next((k for k in spellings(f.name)
                        if params.get(k) is not None), None)
            if key is not None:
                given[f.name], spelled[f.name] = params[key], key
        try:
            options = cls(**given)
            _check_spec(params, options, given)
        except OptionError as err:
            if not err.respell or err.field not in spelled:
                raise
            raise OptionError(spelled[err.field], err.detail) from None
        return options

    @classmethod
    def table(cls) -> str:
        """The options as the markdown table docs/api.md prints."""
        rows = ["| Key | Default | Meaning |", "|---|---|---|"]
        for f in dataclasses.fields(cls):
            default = ("—" if f.default is None
                       else ", ".join(f.default)
                       if isinstance(f.default, tuple)
                       else str(f.default).lower())
            rows.append(f"| `{f.name}` | {default} | {f.metadata['doc']} |")
        return "\n".join(rows)


def _coerce(f: dataclasses.Field, val):
    """A field's value in its declared type (YAML quotes numbers freely),
    inside its floor or allowed values."""
    kind = f.type.removeprefix("Optional[").removesuffix("]")
    if val is None:
        if f.default is None:
            return None
        raise OptionError(f.name, "None is not a value (leave the key out "
                          f"for the default {f.default!r})")
    choices, floor = f.metadata["choices"], f.metadata["floor"]
    if choices:
        if isinstance(val, bool):     # a YAML / JSON / Python boolean
            val = choices[1] if val else choices[0]
        if str(val) not in choices:
            raise OptionError(
                f.name, f"{val!r} is not one of {'|'.join(choices)}")
        return str(val)
    if kind == "bool":
        text = str(val).lower()
        if text not in ("true", "false", "1", "0"):
            raise OptionError(f.name, f"{val!r} is not a boolean")
        return text in ("true", "1")
    if kind in ("int", "float"):
        try:
            num = int(val) if kind == "int" else float(val)
        except (TypeError, ValueError):
            raise OptionError(
                f.name, f"{val!r} is not "
                f"{'an integer' if kind == 'int' else 'a number'}") \
                from None
        if floor is not None and num < floor:
            raise OptionError(f.name, f"{val} must be >= {floor}")
        return num
    if kind == "str":
        return str(val)
    if isinstance(val, str):        # lora_targets, comma-separated
        val = [t.strip() for t in val.split(",")]
    return tuple(val)


def _check_spec(params: dict, options: ServeOptions, given: dict) -> None:
    """What only a spec can get wrong: a key that is set and serves
    nothing, and the `adapter` key (read by serve/api.load_model, which
    folds that adapter into the weights) beside a pool."""
    adapter = params.get("adapter")
    if adapter is not None and (not isinstance(adapter, str)
                                or not adapter.strip()):
        raise OptionError("adapter", f"{adapter!r} must be a non-empty path")
    if options.adapter_pool == 0:
        for name in ("lora_rank", "adapter_dir"):
            if name in given:
                raise OptionError(
                    name, "only applies to a pooled engine; set "
                    "adapter_pool >= 1 (docs/multi-tenant-lora.md)")
    elif adapter is not None:
        raise OptionError(
            "adapter", "cannot combine with adapter_pool on one Server — "
            "the load-time fold serves ONE tenant, the pool serves "
            "per-request adapters; point tenant Servers at this pool via "
            "spec.engineRef instead (docs/multi-tenant-lora.md)")
    if options.grammar == "off" and "grammar_cache_size" in given:
        raise OptionError(
            "grammar_cache_size", "only applies with grammar: on "
            "(docs/structured-output.md)")
