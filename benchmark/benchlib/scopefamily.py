"""Device time under one FAMILY of the program's inner scopes (`swa.*`,
and any later mixer's `<family>.<part>`), for the readers of PR 32.

`hostspans.SCOPES` is a closed list that names an operation by the
innermost LISTED scope, so there a window layer's parts are part of
`attn`. This reads the same capture once more (`hostspans.read_capture`:
plain Python, no JAX) and sums the leaf operations whose op name holds an
inner scope `<family>.<part>`, by scope and by program. The family is an
argument: `linattn.py` and `sparse.py` are this reduction with their
pattern written in. On a program without the family's scopes (the parent
of PR 32, a model without such layers), outside a traced run, or without
a capture, every function here returns None.
"""

from __future__ import annotations

import bisect
import collections
import json
import re

from benchlib import hostspans, spanread, tracefile


def scope_of(op_name: str, family: str) -> str:
    """The innermost `<family>.*` scope in an op name, or ''."""
    found = re.findall(
        r"(?:^|[/()])(" + re.escape(family) + r"\.[a-z_]+)(?=[/()]|$)",
        op_name or "")
    return found[-1] if found else ""


def reduce_ops(devices: list, family: str) -> dict:
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
            scope = scope_of(op_name, family)
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


def reduction(ctx: dict, family: str):
    """`reduce_ops` of this run's capture for one family, made once a run
    (kept in ctx) and printed beside the metrics; None where there is
    nothing to read."""
    if not ctx.get("trace") or not ctx.get("cell"):
        return None
    key = "_scopefamily_" + family
    if key not in ctx:
        capture = spanread.find_capture(ctx["cell"])
        red = None
        if capture is not None:
            devices, _, _, _ = hostspans.read_capture(capture)
            red = reduce_ops(devices, family) if devices else None
        if red and red["scope_s"] and red["op_s"]:
            op = red["op_s"]
            print(f"bench: {family}: device time by program x scope, % of "
                  "operation time: " + json.dumps(
                      {k: round(100 * v / op, 2) for k, v in sorted(
                          red["program_scope_s"].items(),
                          key=lambda kv: -kv[1])}), flush=True)
        else:
            red = None
        ctx[key] = red
    return ctx[key]


def scope_seconds(ctx: dict, family: str, part: str):
    """Seconds under `<family>.<part>`, every program; None without."""
    red = reduction(ctx, family)
    return ((red or {}).get("scope_s", {}).get(f"{family}.{part}")) or None


def family_seconds(ctx: dict, family: str):
    """(seconds under `<family>.*`, all operation seconds), or None."""
    red = reduction(ctx, family)
    if not red:
        return None
    secs = sum(red["scope_s"].values())
    return (secs, red["op_s"]) if secs else None
