"""Device time under the program's `moe.*` and `mla.*` scopes (the sparse
FFN of models/moe.py and the latent-attention mixer of
models/transformer.py), and the window's share of the sparse layers'
counters, for the readers of PR 30.

`hostspans.SCOPES` is a closed list that names an operation by the
innermost LISTED scope, so there the experts are part of `ffn` and latent
attention part of `attn`. This reads the same capture once more
(`hostspans.read_capture`: plain Python, no JAX) and sums the leaf
operations whose op name holds an inner scope `moe.router` / `.sort` /
`.experts` / `.shared` / `.combine` or `mla.q` / `.kv_down` / `.kv_up` /
`.absorb` / `.core` / `.out`, by scope and by program. On a program
without those scopes (the parent of PR 30, a dense model with per-head
attention), outside a traced run, or without a capture, it returns None.
"""

from __future__ import annotations

import bisect
import collections
import json
import re

from benchlib import hostspans, spanread, tracefile

_SCOPE = re.compile(r"(?:^|[/()])((?:moe|mla)\.[a-z_]+)(?=[/()]|$)")


def scope_of(op_name: str) -> str:
    """The innermost `moe.*` / `mla.*` scope in an op name, or ''."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else ""


def reduce_ops(devices: list) -> dict:
    """devices as `hostspans.read_capture` gives them. Seconds are means
    over the devices, leaf operations only, as hostspans' are."""
    by_scope = collections.defaultdict(float)
    by_prog = collections.defaultdict(float)
    total = 0.0
    for dev in devices:
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        for (name, s, d), op_name in zip(dev["ops"], dev["op_names"]):
            if d <= 0 or tracefile.op_kind(name) in tracefile._ENCLOSING:
                continue
            total += d
            scope = scope_of(op_name)
            if not scope:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = (tracefile.program_name(modules[i][0])
                    if i >= 0 and s <= modules[i][1] + modules[i][2]
                    else "no_program")
            by_scope[scope] += d
            by_prog[f"{prog}/{scope}"] += d
    n = max(len(devices), 1)
    sec = lambda ns: ns / 1e9 / n  # noqa: E731
    return {"op_s": sec(total),
            "scope_s": {k: sec(v) for k, v in by_scope.items()},
            "program_scope_s": {k: sec(v) for k, v in by_prog.items()}}


def reduction(ctx: dict):
    """`reduce_ops` of this run's capture, made once a run (kept in ctx)
    and printed beside the metrics; None where there is nothing to read."""
    if not ctx.get("trace") or not ctx.get("cell"):
        return None
    if "_sparse" not in ctx:
        capture = spanread.find_capture(ctx["cell"])
        red = None
        if capture is not None:
            devices, _, _, _ = hostspans.read_capture(capture)
            red = reduce_ops(devices) if devices else None
        if red and red["scope_s"] and red["op_s"]:
            op = red["op_s"]
            print("bench: sparse: device time by program x scope, % of "
                  "operation time: " + json.dumps(
                      {k: round(100 * v / op, 2) for k, v in sorted(
                          red["program_scope_s"].items(),
                          key=lambda kv: -kv[1])}), flush=True)
        else:
            red = None
        ctx["_sparse"] = red
    return ctx["_sparse"]


def family_seconds(ctx: dict, family: str):
    """(seconds under `<family>.*`, all operation seconds), or None."""
    red = reduction(ctx)
    if not red:
        return None
    secs = sum(v for k, v in red["scope_s"].items()
               if k.startswith(family + "."))
    return (secs, red["op_s"]) if secs else None


def traced_tokens(ctx: dict):
    """(requests prefilled in the traced window, tokens decoded in it with
    the context each was decoded at): what the window asked the program
    for, never a bucket's padding, a padding row or a parked row."""
    from benchlib import arith

    window = ctx.get("trace_window")
    if not window:
        return [], []
    prefills = arith.prefilled_in(ctx.get("all_records"), window)
    contexts = [r["prompt_tokens"] + i
                for r in ctx.get("all_records") or []
                for i, t in enumerate((r.get("token_times") or [])[1:], 1)
                if window[0] <= t < window[1]]
    return prefills, contexts


def counters(ctx: dict):
    """The measured window's sparse-layer counters by family (summed over
    label sets, as `prom.parse` reads them), or None without them."""
    c = ctx.get("counters") or {}
    total = c.get("serve_moe_assignments_total")
    if not total:
        return None
    if "_sparse_counters_said" not in ctx:
        ctx["_sparse_counters_said"] = True
        calls = c.get("serve_moe_expert_calls_total", 0.0)
        print("bench: sparse: assignments held here / all "
              f"{c.get('serve_moe_expert_tokens_total', 0.0) / total:.4f} "
              f"of {total:.0f}; (layer, expert) pairs hit / offered "
              f"{c.get('serve_moe_expert_hits_total', 0.0) / calls if calls else 0:.4f}",
              flush=True)
    return c
