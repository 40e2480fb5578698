"""Program contracts: abstract audit of the registered hot programs.

The steady-state program set — engine prefill/decode/prefix-build per
bucket/view (serve/engine.py's module-level ``make_*_fn`` factories),
the train step, the LoRA step — is traced ABSTRACTLY here:
``jax.eval_shape`` builds ShapeDtypeStruct trees and ``jax.make_jaxpr``
stages each program out. Zero device arrays, zero XLA backend compiles
(`rbt check` asserts this via the PR-7 compile sentinel), so the audit
runs in CI in seconds while covering exactly the bodies the engine jits
(the factories are shared — the engine cannot ship a program this audit
never saw).

Per-program checks on the jaxpr (recursing through pjit/scan/cond/remat
sub-jaxprs):

- **program-callback**: host callbacks (``pure_callback``,
  ``io_callback``, ``jax.debug.print``/``debug_callback``) have no place
  in a steady-state program — each invocation is a device→host round
  trip per dispatch.
- **program-dtype**: a silent low-precision→f32 upcast
  (``convert_element_type``) materializing a tensor above
  ``f32_upcast_bytes`` — the "stray f32 promotion in a bf16 program"
  class. Intentional f32 accumulators (dot_general with
  ``preferred_element_type``, scalar loss/LSE accumulators, norms over
  small activations) stay under the threshold by construction.
- **program-const**: closure-captured constants above ``const_bytes``
  embedded in the jaxpr — they bloat every compile and pin HBM per
  compiled variant (weights must be *arguments*).
- **program-census-drift**: the signature cardinality per program
  (buckets × row counts, decode views, auto-prefix splice set) and the
  per-program flags must match ``config/program_baseline.json`` —
  the compiled-program census is a budget, and silent growth is a
  compile-time regression nobody notices until readiness stalls
  (arXiv:2011.03641's compilation-discipline lesson). Regenerate with
  ``rbt check --write-baseline`` when growth is intentional.

Static-shape discipline is asserted structurally: every traced aval must
have a concrete integer shape (no dynamic dims).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import math
import os
from typing import Any, Dict, List, Optional, Tuple

from runbooks_tpu.analysis.findings import Finding

# Dtypes whose silent widening to f32 we audit.
LOW_PRECISION = {"bfloat16", "float16", "int8", "uint8", "int4", "uint4"}


@dataclasses.dataclass(frozen=True)
class AuditSettings:
    """Shapes the audit traces at. Small on purpose — the contracts under
    test (callbacks, promotions, constants, census cardinality) are
    shape-independent, and small shapes keep intentional f32 accumulators
    (norm/LSE upcasts) under the byte thresholds so only genuinely large
    silent promotions flag."""
    config: str = "debug"
    # A model with a layer pattern (linear-attention layers beside full
    # ones): its prefill and decode programs carry the recurrent cache
    # leaves (`state`, `conv`) and the token mask (docs/hybrid-models.md).
    hybrid_config: str = "debug-hybrid"
    sparse_latent_config: str = "debug-sparse-latent"
    window_full_config: str = "debug-window-full"
    max_slots: int = 2
    decode_chunk: int = 2
    # Speculative verify window (serve/engine.py make_verify_fn): the
    # audit traces the verify factories at this K — the max reachable
    # shape, matching the backend default (utils/hw.backend_tuning).
    draft_tokens: int = 4
    # Multi-tenant LoRA pool (serve/lora_pool.py): the adapter-aware
    # program variants are audited at this pool size and rank bucket —
    # the max shapes a pooled engine ships (docs/multi-tenant-lora.md).
    adapter_pool: int = 2
    lora_rank: int = 8
    batch: int = 2
    seq: int = 64
    f32_upcast_bytes: int = 1 << 20   # 1 MiB
    const_bytes: int = 1 << 20        # 1 MiB


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(value: Any):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr, list(value.consts)
    elif isinstance(value, Jaxpr):
        yield value, []
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_jaxprs(closed) -> List[Tuple[Any, List[Any]]]:
    """Every (jaxpr, consts) pair reachable from a ClosedJaxpr, including
    pjit/scan/while/cond/checkpoint bodies."""
    out: List[Tuple[Any, List[Any]]] = [(closed.jaxpr,
                                         list(closed.consts))]
    stack = [closed.jaxpr]
    while stack:
        jaxpr = stack.pop()
        for eqn in jaxpr.eqns:
            for sub, consts in [
                    p for v in eqn.params.values() for p in _sub_jaxprs(v)]:
                out.append((sub, consts))
                stack.append(sub)
    return out


def _source_hint(eqn) -> str:
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return (f" (traced at "
                    f"{os.path.basename(frame.file_name)}:"
                    f"{frame.start_line})")
    except Exception:  # noqa: BLE001 — the hint is decorative
        pass
    return ""


def audit_jaxpr(closed, program: str,
                settings: AuditSettings) -> Tuple[List[Finding], dict]:
    """Content checks over one program's closed jaxpr. Returns
    (findings, flags) with flags = {callbacks, f32_upcasts,
    const_bytes_max} — the numbers the census baseline pins."""
    path = f"program:{program}"
    findings: List[Finding] = []
    callbacks = 0
    upcasts = 0
    const_max = 0
    for jaxpr, consts in iter_jaxprs(closed):
        for var, const in zip(jaxpr.constvars, consts):
            nbytes = getattr(const, "nbytes", None)
            if nbytes is None:
                size = getattr(const, "size", 0) or 0
                item = getattr(getattr(const, "dtype", None),
                               "itemsize", 1)
                nbytes = int(size) * int(item)
            const_max = max(const_max, int(nbytes))
            if nbytes >= settings.const_bytes:
                findings.append(Finding(
                    rule="program-const", path=path, line=0,
                    message=f"closure-captured constant of {nbytes} bytes "
                            f"(shape {getattr(const, 'shape', '?')}) "
                            "embedded in the jaxpr — it bloats every "
                            "compile and pins HBM per variant; pass it as "
                            "an argument"))
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            # jax.debug.print is its own primitive (debug_print), not a
            # *_callback one.
            if "callback" in name or name == "debug_print":
                callbacks += 1
                findings.append(Finding(
                    rule="program-callback", path=path, line=0,
                    message=f"host callback `{name}` in a steady-state "
                            "program — a device→host round trip per "
                            f"dispatch{_source_hint(eqn)}"))
                continue
            if name != "convert_element_type" or not eqn.invars:
                continue
            in_aval = getattr(eqn.invars[0], "aval", None)
            out_aval = getattr(eqn.outvars[0], "aval", None)
            if in_aval is None or out_aval is None:
                continue
            if str(getattr(in_aval, "dtype", "")) not in LOW_PRECISION:
                continue
            if str(getattr(out_aval, "dtype", "")) != "float32":
                continue
            nbytes = int(math.prod(out_aval.shape)) * 4
            if nbytes >= settings.f32_upcast_bytes:
                upcasts += 1
                findings.append(Finding(
                    rule="program-dtype", path=path, line=0,
                    message=f"silent {in_aval.dtype}→float32 upcast "
                            f"materializing {nbytes} bytes "
                            f"(shape {tuple(out_aval.shape)})"
                            f"{_source_hint(eqn)}; accumulate explicitly "
                            "(preferred_element_type) or keep the tensor "
                            "in the low dtype"))
        for var in list(jaxpr.invars) + list(jaxpr.outvars):
            shape = getattr(getattr(var, "aval", None), "shape", ())
            if not all(isinstance(d, int) for d in shape):
                findings.append(Finding(
                    rule="program-shape", path=path, line=0,
                    message=f"non-static dimension in {shape}: the "
                            "engine's compiled-program census assumes "
                            "static shapes everywhere"))
    return findings, {"callbacks": callbacks, "f32_upcasts": upcasts,
                      "const_bytes_max": const_max}


# ---------------------------------------------------------------------------
# The audited program set
# ---------------------------------------------------------------------------

def _key_sds():
    import jax

    return jax.eval_shape(lambda: jax.random.key(0))


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _engine_specs(settings: AuditSettings) -> List[dict]:
    """(name, fn, args, signatures) for the serve engine's program set —
    built from the same module-level factories and bucket helpers the
    engine itself uses (serve/engine.py)."""
    import jax.numpy as jnp

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import KVCache, init_params
    from runbooks_tpu.serve.engine import (
        _buckets,
        auto_prefix_plens,
        bucket_for,
        dispatch_shapes,
        make_decode_fn,
        make_prefill_fn,
        make_prefix_build_fn,
        make_verify_fn,
        pack_decode_fn,
        view_buckets_for,
    )
    import jax

    cfg = get_config(settings.config)
    max_seq_len = cfg.max_seq_len
    cache_len = max_seq_len + 1
    slots = settings.max_slots
    buckets = _buckets(max_seq_len)
    views = view_buckets_for(max_seq_len)
    rows_set = (1, slots) if slots > 1 else (1,)
    # What admission can dispatch at the default budget (the window).
    n_prefill = len(dispatch_shapes(buckets, max_seq_len, slots))

    key = _key_sds()
    params = jax.eval_shape(functools.partial(init_params, cfg), key)
    pool = jax.eval_shape(lambda: KVCache.create(
        cfg, slots, max_seq_len, trash_slot=True, quantize_kv=False))

    def prefill_args(rows: int, bucket: int, plen: int = 0):
        args = [params, pool,
                _sds((rows, bucket), jnp.int32),
                _sds((rows, bucket), jnp.int32),
                _sds((rows,), jnp.int32), _sds((rows,), jnp.int32),
                key, _sds((rows,), jnp.float32),
                _sds((rows,), jnp.int32), _sds((rows,), jnp.float32)]
        if plen:
            kv = (cfg.num_layers, plen, cfg.num_kv_heads, cfg.head_dim)
            args += [_sds(kv, cfg.activation_dtype),
                     _sds(kv, cfg.activation_dtype)]
        return args

    prefill = make_prefill_fn(cfg, cache_len)
    # The auto-prefix splice set: every (plen, suffix bucket, rows) the
    # quantized registration path can produce — the bounded census
    # warmup and the worker's background warms walk (engine
    # prefix_warmup_shapes).
    plens = auto_prefix_plens(buckets, max_seq_len)
    splice = [(p, b, r) for p in plens for b in buckets
              if b <= bucket_for(buckets, max_seq_len - p)
              for r in rows_set]
    rep_plen, rep_bucket, rep_rows = splice[-1] if splice \
        else (16, buckets[0], 1)

    # Decode programs are audited as the engine jits them: over the two
    # packed per-slot blocks (pack_decode_fn), the adapter lanes a row.
    def packed_decode(*factory_args):
        return pack_decode_fn(make_decode_fn(*factory_args))

    decode = packed_decode(cfg, settings.decode_chunk, max_seq_len,
                           max_seq_len, views[-1])
    decode_args = [params, pool, _sds((7, slots), jnp.int32),
                   _sds((2, slots), jnp.float32), key]

    prefix_build = make_prefix_build_fn(cfg, cache_len)

    def prefix_splice(p, pool_, pk, pv, *rest):
        return prefill(p, pool_, *rest, pk=pk, pv=pv)

    rest = prefill_args(rep_rows, rep_bucket, plen=rep_plen)

    # Speculative verify (serve/engine.py make_verify_fn): audited at
    # max K (settings.draft_tokens) and the widest row set — one
    # compiled program per decode view, same census shape as decode.
    K = settings.draft_tokens
    verify = make_verify_fn(cfg, K, max_seq_len, views[-1])
    verify_args = [params, pool,
                   _sds((slots, K + 1), jnp.int32),
                   _sds((slots,), jnp.int32), _sds((slots,), jnp.int32),
                   key, _sds((slots,), jnp.float32),
                   _sds((slots,), jnp.int32), _sds((slots,), jnp.float32),
                   _sds((slots,), jnp.bool_)]

    # Paged engine (serve/paging.py): same audit discipline — the paged
    # factories are the bodies the paged engine jits, traced at their
    # most complex reachable shape (largest prefix-page bucket splice;
    # one decode view). Census cardinality comes from the same
    # enumeration helpers warmup walks.
    from runbooks_tpu.serve.paging import (
        PagePool,
        make_kv_swap_in_fn,
        make_kv_swap_out_fn,
        make_paged_decode_fn,
        make_paged_prefill_fn,
        make_paged_verify_fn,
        paged_prefill_shapes,
        view_page_buckets_for,
    )

    page_size = 16
    mpps = max_seq_len // page_size
    pool_pages = slots * mpps
    paged_pool = jax.eval_shape(lambda: PagePool.create(
        cfg, pool_pages, page_size, quantize_kv=False))
    pshapes = paged_prefill_shapes(buckets, mpps, page_size, max_seq_len)
    vp_buckets = view_page_buckets_for(max_seq_len, page_size)
    # Widest gather first: the splice cost scales with the prefix-page
    # bucket (ppb*page_size gathered rows), so audit at max ppb and the
    # largest suffix bucket reachable alongside it.
    rep_ppb, rep_b = max((p, b) for b, p in pshapes if p)
    paged_prefill = make_paged_prefill_fn(cfg, cache_len, page_size,
                                          pool_pages)
    paged_prefill_args = [
        params, paged_pool,
        _sds((slots, rep_b), jnp.int32), _sds((slots, rep_b), jnp.int32),
        _sds((slots, mpps), jnp.int32), _sds((slots,), jnp.int32),
        key, _sds((slots,), jnp.float32), _sds((slots,), jnp.int32),
        _sds((slots,), jnp.float32),
        _sds((slots, rep_ppb), jnp.int32), _sds((slots,), jnp.int32)]
    paged_decode = pack_decode_fn(make_paged_decode_fn(
        cfg, settings.decode_chunk, max_seq_len, page_size,
        vp_buckets[-1], pool_pages))
    paged_decode_args = [
        params, paged_pool, _sds((slots, mpps), jnp.int32),
        *decode_args[2:]]
    paged_verify = make_paged_verify_fn(cfg, K, page_size,
                                        vp_buckets[-1], pool_pages)
    paged_verify_args = [
        params, paged_pool, _sds((slots, mpps), jnp.int32),
        _sds((slots, K + 1), jnp.int32),
        _sds((slots,), jnp.int32), _sds((slots,), jnp.int32), key,
        _sds((slots,), jnp.float32), _sds((slots,), jnp.int32),
        _sds((slots,), jnp.float32), _sds((slots,), jnp.bool_)]

    # Host-tier swap splices (docs/paged-kv.md "Host tier and
    # preemption"): the page index is a traced operand, so each
    # direction is ONE program for every page — signature cardinality 1.
    # The swap-in payload operands mirror the host buffers (one page's
    # K/V, pool dtype, numpy-backed at runtime).
    kv_swap_out = make_kv_swap_out_fn()
    kv_swap_in = make_kv_swap_in_fn()
    page_shape = (paged_pool.k.shape[0],) + paged_pool.k.shape[2:]
    kv_swap_in_args = [paged_pool, _sds((), jnp.int32),
                       _sds(page_shape, paged_pool.k.dtype),
                       _sds(page_shape, paged_pool.v.dtype)]

    # Multi-tenant LoRA adapter variants (docs/multi-tenant-lora.md): a
    # pooled engine jits THESE shapes instead of the plain set — same
    # factories, adapter-pool + lane-index operands live. Audited at
    # settings.adapter_pool/lora_rank (the max reachable pool shapes);
    # signature cardinality matches the plain programs 1:1 (the pool
    # replaces, never multiplies, the census).
    from runbooks_tpu.api.serve_params import ServeOptions
    from runbooks_tpu.ops.lora import init_adapter_pool

    apool = jax.eval_shape(lambda: init_adapter_pool(
        cfg, settings.adapter_pool, settings.lora_rank,
        ServeOptions().lora_targets))

    def aslots_sds(rows):
        return _sds((rows,), jnp.int32)

    def adapter_prefill(params_, pool_, apool_, aslots_, *rest):
        return prefill(params_, pool_, *rest, apool=apool_,
                       aslots=aslots_)

    def adapter_decode(params_, pool_, apool_, *rest):
        return decode(params_, pool_, *rest, apool=apool_)

    def adapter_verify(params_, pool_, apool_, aslots_, *rest):
        return verify(params_, pool_, *rest, apool=apool_,
                      aslots=aslots_)

    def paged_adapter_prefill(params_, pool_, apool_, aslots_, *rest):
        return paged_prefill(params_, pool_, *rest, apool=apool_,
                             aslots=aslots_)

    def paged_adapter_decode(params_, pool_, apool_, *rest):
        return paged_decode(params_, pool_, *rest, apool=apool_)

    def paged_adapter_verify(params_, pool_, apool_, aslots_, *rest):
        return paged_verify(params_, pool_, *rest, apool=apool_,
                            aslots=aslots_)

    # Grammar-constrained decoding (serve/grammar.py,
    # docs/structured-output.md): a grammar-on engine jits THESE shapes
    # instead of the plain set — the gmask bool operand rides every
    # dispatch (all-True rows for unconstrained lanes), so like the
    # adapter variants above it replaces, never multiplies, the census.
    vocab = cfg.vocab_size

    def gmask_sds(*shape):
        return _sds(shape, jnp.bool_)

    def grammar_prefill(params_, pool_, gmask_, *rest):
        return prefill(params_, pool_, *rest, gmask=gmask_)

    def grammar_decode(params_, pool_, gmask_, *rest):
        return decode(params_, pool_, *rest, gmask=gmask_)

    def grammar_verify(params_, pool_, gmask_, *rest):
        return verify(params_, pool_, *rest, gmask=gmask_)

    def paged_grammar_prefill(params_, pool_, gmask_, *rest):
        return paged_prefill(params_, pool_, *rest, gmask=gmask_)

    def paged_grammar_decode(params_, pool_, gmask_, *rest):
        return paged_decode(params_, pool_, *rest, gmask=gmask_)

    def paged_grammar_verify(params_, pool_, gmask_, *rest):
        return paged_verify(params_, pool_, *rest, gmask=gmask_)

    specs = [
        {"component": "serve", "name": "prefill", "fn": prefill,
         "args": prefill_args(rows_set[-1], buckets[-1]),
         "signatures": n_prefill},
        {"component": "serve", "name": "prefill_prefix",
         "fn": prefix_splice,
         "args": rest[:2] + rest[-2:] + rest[2:-2],
         "signatures": len(splice)},
        {"component": "serve", "name": "decode", "fn": decode,
         "args": decode_args, "signatures": len(views)},
        {"component": "serve", "name": "prefix_build", "fn": prefix_build,
         "args": [params, _sds((1, buckets[-1]), jnp.int32),
                  _sds((1, buckets[-1]), jnp.int32)],
         "signatures": len(buckets)},
        {"component": "serve", "name": "paged_prefill",
         "fn": paged_prefill, "args": paged_prefill_args,
         "signatures": len(pshapes) * len(rows_set)},
        {"component": "serve", "name": "paged_decode",
         "fn": paged_decode, "args": paged_decode_args,
         "signatures": len(vp_buckets)},
        {"component": "serve", "name": "verify", "fn": verify,
         "args": verify_args, "signatures": len(views)},
        {"component": "serve", "name": "paged_verify",
         "fn": paged_verify, "args": paged_verify_args,
         "signatures": len(vp_buckets)},
        {"component": "serve", "name": "kv_swap_out", "fn": kv_swap_out,
         "args": [paged_pool, _sds((), jnp.int32)], "signatures": 1},
        {"component": "serve", "name": "kv_swap_in", "fn": kv_swap_in,
         "args": kv_swap_in_args, "signatures": 1},
        {"component": "serve", "name": "adapter_prefill",
         "fn": adapter_prefill,
         "args": ([params, pool, apool, aslots_sds(rows_set[-1])]
                  + prefill_args(rows_set[-1], buckets[-1])[2:]),
         "signatures": n_prefill},
        {"component": "serve", "name": "adapter_decode",
         "fn": adapter_decode,
         "args": [params, pool, apool] + decode_args[2:],
         "signatures": len(views)},
        {"component": "serve", "name": "adapter_verify",
         "fn": adapter_verify,
         "args": ([params, pool, apool, aslots_sds(slots)]
                  + verify_args[2:]),
         "signatures": len(views)},
        {"component": "serve", "name": "paged_adapter_prefill",
         "fn": paged_adapter_prefill,
         "args": ([params, paged_pool, apool, aslots_sds(slots)]
                  + paged_prefill_args[2:]),
         "signatures": len(pshapes) * len(rows_set)},
        {"component": "serve", "name": "paged_adapter_decode",
         "fn": paged_adapter_decode,
         "args": [params, paged_pool, apool] + paged_decode_args[2:],
         "signatures": len(vp_buckets)},
        {"component": "serve", "name": "paged_adapter_verify",
         "fn": paged_adapter_verify,
         "args": ([params, paged_pool, apool, aslots_sds(slots)]
                  + paged_verify_args[2:]),
         "signatures": len(vp_buckets)},
        {"component": "serve", "name": "grammar_prefill",
         "fn": grammar_prefill,
         "args": ([params, pool, gmask_sds(rows_set[-1], vocab)]
                  + prefill_args(rows_set[-1], buckets[-1])[2:]),
         "signatures": n_prefill},
        {"component": "serve", "name": "grammar_decode",
         "fn": grammar_decode,
         "args": ([params, pool, gmask_sds(slots, vocab)]
                  + decode_args[2:]),
         "signatures": len(views)},
        {"component": "serve", "name": "grammar_verify",
         "fn": grammar_verify,
         "args": ([params, pool, gmask_sds(slots, K + 1, vocab)]
                  + verify_args[2:]),
         "signatures": len(views)},
        {"component": "serve", "name": "paged_grammar_prefill",
         "fn": paged_grammar_prefill,
         "args": ([params, paged_pool, gmask_sds(slots, vocab)]
                  + paged_prefill_args[2:]),
         "signatures": len(pshapes) * len(rows_set)},
        {"component": "serve", "name": "paged_grammar_decode",
         "fn": paged_grammar_decode,
         "args": ([params, paged_pool, gmask_sds(slots, vocab)]
                  + paged_decode_args[2:]),
         "signatures": len(vp_buckets)},
        {"component": "serve", "name": "paged_grammar_verify",
         "fn": paged_grammar_verify,
         "args": ([params, paged_pool, gmask_sds(slots, K + 1, vocab)]
                  + paged_verify_args[2:]),
         "signatures": len(vp_buckets)},
    ]

    # Sharded serving mesh (docs/tensor-parallel-performance.md): under a
    # mesh_tensor > 1 mesh the SAME factories trace DIFFERENT programs —
    # resolve_collective_matmul flips the ring path on at trace time — so
    # the sharded decode path gets its own census rows, traced under a
    # real tensor=2 mesh exactly as the engine's warmup does. Signature
    # cardinality mirrors the unsharded counterparts (a mesh engine
    # compiles the same bucket walk, just different programs). Skipped
    # below 2 devices; the canonical check env (Makefile TEST_ENV) pins 8
    # virtual CPU devices, so the committed baseline always carries them.
    if len(jax.devices()) >= 2:
        import dataclasses as _dc

        from runbooks_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(data=1, fsdp=-1, tensor=2))
        cfg_tp = _dc.replace(cfg, collective_matmul="auto")

        prefill_tp = make_prefill_fn(cfg_tp, cache_len)
        decode_tp = packed_decode(cfg_tp, settings.decode_chunk,
                                  max_seq_len, max_seq_len, views[-1])
        verify_tp = make_verify_fn(cfg_tp, K, max_seq_len, views[-1])
        paged_prefill_tp = make_paged_prefill_fn(cfg_tp, cache_len,
                                                 page_size, pool_pages)
        paged_decode_tp = pack_decode_fn(make_paged_decode_fn(
            cfg_tp, settings.decode_chunk, max_seq_len, page_size,
            vp_buckets[-1], pool_pages))
        paged_verify_tp = make_paged_verify_fn(cfg_tp, K, page_size,
                                               vp_buckets[-1], pool_pages)

        def adapter_decode_tp(params_, pool_, apool_, *rest):
            return decode_tp(params_, pool_, *rest, apool=apool_)

        specs += [
            {"component": "serve", "name": "prefill_sharded",
             "fn": prefill_tp, "mesh": mesh,
             "args": prefill_args(rows_set[-1], buckets[-1]),
             "signatures": n_prefill},
            {"component": "serve", "name": "decode_sharded",
             "fn": decode_tp, "mesh": mesh, "args": decode_args,
             "signatures": len(views)},
            {"component": "serve", "name": "verify_sharded",
             "fn": verify_tp, "mesh": mesh, "args": verify_args,
             "signatures": len(views)},
            {"component": "serve", "name": "paged_prefill_sharded",
             "fn": paged_prefill_tp, "mesh": mesh,
             "args": paged_prefill_args,
             "signatures": len(pshapes) * len(rows_set)},
            {"component": "serve", "name": "paged_decode_sharded",
             "fn": paged_decode_tp, "mesh": mesh,
             "args": paged_decode_args, "signatures": len(vp_buckets)},
            {"component": "serve", "name": "paged_verify_sharded",
             "fn": paged_verify_tp, "mesh": mesh,
             "args": paged_verify_args, "signatures": len(vp_buckets)},
            {"component": "serve", "name": "adapter_decode_sharded",
             "fn": adapter_decode_tp, "mesh": mesh,
             "args": [params, pool, apool] + decode_args[2:],
             "signatures": len(views)},
        ]
    # A layer pattern with recurrent state: the same two factories trace
    # other programs (a scan over periods, the state splice, the token
    # mask). The engine refuses the prefix, verify, adapter and paged
    # variants for such a model, so these two are its whole set.
    cfg_h = get_config(settings.hybrid_config)
    params_h = jax.eval_shape(functools.partial(init_params, cfg_h), key)
    pool_h = jax.eval_shape(lambda: KVCache.create(
        cfg_h, slots, cfg_h.max_seq_len, trash_slot=True))
    buckets_h = _buckets(cfg_h.max_seq_len)
    views_h = view_buckets_for(cfg_h.max_seq_len)
    specs += [
        {"component": "serve", "name": "hybrid_prefill",
         "fn": make_prefill_fn(cfg_h, cfg_h.max_seq_len + 1),
         "args": [params_h, pool_h] + prefill_args(
             rows_set[-1], buckets_h[-1])[2:],
         "signatures": len(buckets_h) * len(rows_set)},
        {"component": "serve", "name": "hybrid_decode",
         "fn": packed_decode(cfg_h, settings.decode_chunk,
                             cfg_h.max_seq_len, cfg_h.max_seq_len,
                             views_h[-1]),
         "args": [params_h, pool_h] + decode_args[2:],
         "signatures": len(views_h)},
    ]
    # Latent attention, a leading dense layer and sparse FFNs held as a
    # share: again the same two factories, other programs (the latent
    # leaf's splice, the absorbed decode, the grouped product, the counts
    # among the results). The engine refuses the prefix, verify, adapter
    # and paged variants here too.
    cfg_s = get_config(settings.sparse_latent_config, moe_experts_held=8)
    params_s = jax.eval_shape(functools.partial(init_params, cfg_s), key)
    pool_s = jax.eval_shape(lambda: KVCache.create(
        cfg_s, slots, cfg_s.max_seq_len, trash_slot=True))
    buckets_s = _buckets(cfg_s.max_seq_len)
    views_s = view_buckets_for(cfg_s.max_seq_len)
    specs += [
        {"component": "serve", "name": "sparse_latent_prefill",
         "fn": make_prefill_fn(cfg_s, cfg_s.max_seq_len + 1),
         "args": [params_s, pool_s] + prefill_args(
             rows_set[-1], buckets_s[-1])[2:],
         "signatures": len(buckets_s) * len(rows_set)},
        {"component": "serve", "name": "sparse_latent_decode",
         "fn": packed_decode(cfg_s, settings.decode_chunk,
                             cfg_s.max_seq_len, cfg_s.max_seq_len,
                             views_s[-1]),
         "args": [params_s, pool_s] + decode_args[2:],
         "signatures": len(views_s)},
    ]
    # Window layers with a sink and a ring cache beside full layers of
    # another KV head count: the same two factories once more (the ring
    # leaves' write with dropped tokens and their splice, the ring read
    # under the age mask, a stack a position of the period). The engine
    # refuses the prefix, verify, adapter and paged variants here too
    # (docs/window-full-models.md).
    cfg_w = get_config(settings.window_full_config, moe_experts_held=8)
    params_w = jax.eval_shape(functools.partial(init_params, cfg_w), key)
    pool_w = jax.eval_shape(lambda: KVCache.create(
        cfg_w, slots, cfg_w.max_seq_len, trash_slot=True))
    buckets_w = _buckets(cfg_w.max_seq_len)
    views_w = view_buckets_for(cfg_w.max_seq_len)
    specs += [
        {"component": "serve", "name": "window_full_prefill",
         "fn": make_prefill_fn(cfg_w, cfg_w.max_seq_len + 1),
         "args": [params_w, pool_w] + prefill_args(
             rows_set[-1], buckets_w[-1])[2:],
         "signatures": len(buckets_w) * len(rows_set)},
        {"component": "serve", "name": "window_full_decode",
         "fn": packed_decode(cfg_w, settings.decode_chunk,
                             cfg_w.max_seq_len, cfg_w.max_seq_len,
                             views_w[-1]),
         "args": [params_w, pool_w] + decode_args[2:],
         "signatures": len(views_w)},
    ]
    return specs


def _train_specs(settings: AuditSettings) -> List[dict]:
    import jax
    import jax.numpy as jnp
    import optax

    from runbooks_tpu.models.config import get_config
    from runbooks_tpu.models.transformer import (
        init_params,
        param_logical_axes,
    )
    from runbooks_tpu.parallel.mesh import single_device_mesh
    from runbooks_tpu.parallel.sharding import tree_shardings
    from runbooks_tpu.train.lora import (
        LoraConfig,
        init_lora,
        lora_logical_axes,
        make_lora_train_step,
    )
    from runbooks_tpu.train.step import (
        TrainState,
        infer_state_shardings,
        make_train_step,
    )

    cfg = get_config(settings.config)
    mesh = single_device_mesh()
    optimizer = optax.adamw(1e-3)
    key = _key_sds()
    batch = {"tokens": _sds((settings.batch, settings.seq), jnp.int32),
             "targets": _sds((settings.batch, settings.seq), jnp.int32)}

    def init_fn(rng):
        params = init_params(cfg, rng)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=optimizer.init(params))

    state = jax.eval_shape(init_fn, key)
    shardings = infer_state_shardings(param_logical_axes(cfg), state, mesh)
    step = make_train_step(cfg, optimizer, mesh, shardings)

    lcfg = LoraConfig(rank=4)
    base = state.params
    base_shardings = tree_shardings(base, param_logical_axes(cfg), mesh)

    def lora_init_fn(rng):
        lora = init_lora(base, lcfg, rng)
        return TrainState(step=jnp.zeros((), jnp.int32), params=lora,
                          opt_state=optimizer.init(lora))

    lstate = jax.eval_shape(lora_init_fn, key)
    laxes = lora_logical_axes(lcfg, lstate.params)
    lshardings = infer_state_shardings(laxes, lstate, mesh)
    lstep = make_lora_train_step(cfg, lcfg, optimizer, mesh, lshardings,
                                 base_shardings)

    return [
        {"component": "train", "name": "train_step", "fn": step,
         "args": [state, batch], "signatures": 1},
        {"component": "train", "name": "lora_step", "fn": lstep,
         "args": [lstate, base, batch], "signatures": 1},
    ]


def audit_programs(
    settings: Optional[AuditSettings] = None,
) -> Tuple[dict, List[Finding]]:
    """Trace and audit the full registered program set. Returns
    (census, findings). The census is the committed-baseline content:
    per program, its signature cardinality and content flags."""
    import jax

    settings = settings or AuditSettings()
    findings: List[Finding] = []
    programs: List[dict] = []
    for spec in _engine_specs(settings) + _train_specs(settings):
        program = f"{spec['component']}/{spec['name']}"
        try:
            # Sharded specs trace under their mesh; set_mesh must wrap
            # the trace (it cannot be entered inside one).
            with (jax.set_mesh(spec["mesh"]) if "mesh" in spec
                  else contextlib.nullcontext()):
                closed = jax.make_jaxpr(spec["fn"])(*spec["args"])
        except Exception as exc:  # noqa: BLE001 — surface, don't crash
            findings.append(Finding(
                rule="program-trace", path=f"program:{program}", line=0,
                message=f"abstract trace failed: {exc!r}"))
            programs.append({"component": spec["component"],
                             "name": spec["name"],
                             "signatures": spec["signatures"],
                             "flags": None})
            continue
        prog_findings, flags = audit_jaxpr(closed, program, settings)
        findings.extend(prog_findings)
        programs.append({"component": spec["component"],
                         "name": spec["name"],
                         "signatures": spec["signatures"],
                         "flags": flags})
    census = {
        "settings": {"config": settings.config,
                     "max_slots": settings.max_slots,
                     "decode_chunk": settings.decode_chunk,
                     "draft_tokens": settings.draft_tokens,
                     "adapter_pool": settings.adapter_pool,
                     "lora_rank": settings.lora_rank,
                     "batch": settings.batch, "seq": settings.seq},
        "programs": programs,
    }
    return census, findings


# ---------------------------------------------------------------------------
# Census baseline (config/program_baseline.json)
# ---------------------------------------------------------------------------

def load_program_baseline(path: str) -> Optional[dict]:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def write_program_baseline(path: str, census: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(census, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def diff_census(census: dict, baseline: Optional[dict],
                baseline_path: str) -> List[Finding]:
    """Census drift findings (rule ``program-census-drift``), mirroring
    the metrics-catalog drift gate: additions, removals, and signature/
    flag changes all fail until the committed baseline is regenerated."""
    hint = (f"; regenerate {os.path.basename(baseline_path)} with "
            "`rbt check --write-baseline` if intentional")
    if baseline is None:
        return [Finding(
            rule="program-census-drift", path=baseline_path, line=0,
            message="program baseline missing" + hint)]
    findings: List[Finding] = []
    if baseline.get("settings") != census["settings"]:
        findings.append(Finding(
            rule="program-census-drift", path=baseline_path, line=0,
            message=f"audit settings changed: baseline "
                    f"{baseline.get('settings')} vs "
                    f"{census['settings']}" + hint))
    def by_name(c):
        return {(p["component"], p["name"]): p
                for p in c.get("programs", [])}
    base, cur = by_name(baseline), by_name(census)
    for key in sorted(set(base) | set(cur)):
        name = "/".join(key)
        b, c = base.get(key), cur.get(key)
        if b is None:
            findings.append(Finding(
                rule="program-census-drift", path=baseline_path, line=0,
                message=f"new program {name} not in baseline" + hint))
        elif c is None:
            findings.append(Finding(
                rule="program-census-drift", path=baseline_path, line=0,
                message=f"program {name} vanished from the census" + hint))
        elif (b.get("signatures") != c["signatures"]
              or b.get("flags") != c["flags"]):
            findings.append(Finding(
                rule="program-census-drift", path=baseline_path, line=0,
                message=f"program {name} drifted: baseline "
                        f"signatures={b.get('signatures')} "
                        f"flags={b.get('flags')} vs "
                        f"signatures={c['signatures']} "
                        f"flags={c['flags']}" + hint))
    return findings
