"""From a profiler capture to (a) the device's idle time named by what the
host did and (b) its busy time named by the program's own layers.

Run as a script in a child of the harness, as `tracefile.py` is;
`spanread.py` starts it once per capture and every new reader loads what it
wrote. The file is read by `xspace.py` (plain Python, no JAX: the op names
are in a place `jax.profiler.ProfileData` does not show). The pure part
(`reduce_capture`) works on plain tuples.

What a capture of this program holds besides the device planes that
`tracefile.py` describes (looked at by hand, PR 24):

- `/host:CPU`, one line per thread. While a capture runs, every span of
  `runbooks_tpu/obs/trace.py` is a `TraceAnnotation` there, named as the
  span is, its arguments as stats; the engine worker's line is the one
  with `tick` events, the trainer's the one with `step` events. Other
  events of those lines (the runtime's own) are not ours and are skipped.
- an `XLA Ops` event of a device plane is named by its instruction text,
  which has no metadata; the instruction's op name is the `tf_op` stat of
  the event's *metadata*, and it ends in the `jax.named_scope` stack of
  the program: `jit(prefill_fn)/while/body/closed_call/block/attn/
  attn.rope/convert_element_type:`. An executable loaded from a compile
  cache filled before the scopes existed has op names without them (the
  cache key ignores metadata): its time reads as unscoped.

The traced window is `tracefile.read_xplane`'s (first to last device
event), busy time is the same union of leaf operations, so the shares here
add up to that run's `device_idle_share.*`.
"""

from __future__ import annotations

import bisect
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchlib import tracefile  # noqa: E402

# Every span of the engine worker's and the trainer's thread belongs to one
# family; a parent stands for what is left of it outside its children.
FAMILY = {
    "worker.idle": "no_work",
    "worker.intake": "admit", "tick.admit": "admit",
    "prefill.operands": "operands", "decode.operands": "operands",
    "prefill.dispatch": "runtime", "prefill.sync": "runtime",
    "decode.dispatch": "runtime", "decode.sync": "runtime",
    "verify": "runtime",
    "tick": "bookkeeping", "prefill": "bookkeeping", "decode": "bookkeeping",
    "prefill.activate": "bookkeeping", "decode.replay": "bookkeeping",
    "worker.finish": "bookkeeping",
    "checkpoint": "checkpoint", "emergency_save": "checkpoint",
    "data_wait": "step_host", "step": "step_host", "step.sync": "step_host",
    "log": "step_host", "restore": "step_host",
}
# The thread whose spans name the idle time: the one that holds these.
_LOOP_SPANS = ("tick", "worker.idle", "step")

# The program's named scopes (models/transformer.py, ops/*, serve/engine.py,
# train/step.py, train/lora.py). An operation belongs to the innermost one
# in its op name.
SCOPES = frozenset((
    "embed", "layers", "block", "norm", "attn", "attn.qkv", "attn.rope",
    "attn.kv_write", "attn.core", "attn.out", "attn.mask", "ffn", "head",
    "sample", "kv_splice", "loss", "optimizer", "lora", "flash.fwd",
    "flash.dq", "flash.dkv", "ring.ag", "ring.rs", "ring.ag_bwd",
    "ring.rs_bwd"))


def scope_stack(op_name: str) -> list:
    """The program's scopes in an op name, outermost first. Transforms wrap
    a stack in parentheses (`transpose(jvp(block))/attn/...`), so split on
    those too."""
    return [t for t in re.split(r"[/()]", op_name or "") if t in SCOPES]


def flatten(spans: list) -> list:
    """Nested (name, start, end) spans of one thread -> disjoint, sorted
    (start, end, name) segments, each named by the innermost span over
    it."""
    events = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack = [], []   # stack of [name, end]
    cursor = None

    def emit(upto):
        nonlocal cursor
        if stack and cursor is not None and upto > cursor:
            out.append((cursor, upto, stack[-1][0]))
        cursor = upto

    for name, start, end in events:
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        emit(start)
        cursor = start
        stack.append([name, min(end, stack[-1][1]) if stack else end])
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def overlaps(segments: list, lo: float, hi: float):
    """(name, seconds-in-ns) of each segment's part inside [lo, hi]."""
    starts = [s[0] for s in segments]
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(segments) and segments[i][0] < hi:
        s, e, name = segments[i]
        part = min(e, hi) - max(s, lo)
        if part > 0:
            yield name, part
        i += 1


def device_gaps(ops, t_lo, t_hi) -> list:
    """Idle [start, end] intervals of one device inside the window: the
    complement of tracefile's union of leaf operations."""
    leaf = [(s, s + d) for n, s, d in ops
            if d > 0 and tracefile.op_kind(n) not in tracefile._ENCLOSING]
    edges = [t_lo] + [x for iv in tracefile.union(leaf) for x in iv] + [t_hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def reduce_capture(devices: list, spans: list, t_lo: float,
                   t_hi: float) -> dict:
    """devices: one dict per device plane with `ops` (name, start_ns,
    dur_ns), `modules`, and `op_names` (the op name of each op, same
    order). spans: (name, start_ns, end_ns) of the loop thread, ours only.
    Seconds are means over the devices, as tracefile's are."""
    n = len(devices)
    window = t_hi - t_lo
    segments = flatten(spans)
    idle_by_span = collections.defaultdict(float)
    scope_ns = collections.defaultdict(float)
    prog_scope_ns = collections.defaultdict(float)
    under = collections.defaultdict(float)   # time under each scope at all
    unscoped_ns = collections.defaultdict(float)
    gaps_all, idle_ns, busy_ns = [], 0.0, 0.0
    for di, dev in enumerate(devices):
        for lo, hi in device_gaps(dev["ops"], t_lo, t_hi):
            idle_ns += hi - lo
            named = collections.defaultdict(float)
            for name, part in overlaps(segments, lo, hi):
                named[name] += part
            for name, part in named.items():
                idle_by_span[name] += part
            rest = (hi - lo) - sum(named.values())
            if rest > 0:
                idle_by_span[""] += rest
                named[""] = rest
            gaps_all.append((hi - lo, lo, di, dict(named)))
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        for (name, s, d), op_name in zip(dev["ops"], dev["op_names"]):
            if d <= 0 or tracefile.op_kind(name) in tracefile._ENCLOSING:
                continue
            busy_ns += d
            stack = scope_stack(op_name)
            inner = stack[-1] if stack else ""
            scope_ns[inner] += d
            for sc in set(stack):
                under[sc] += d
            i = bisect.bisect_right(starts, s) - 1
            prog = (tracefile.program_name(modules[i][0])
                    if i >= 0 and s <= modules[i][1] + modules[i][2]
                    else "no_program")
            prog_scope_ns[f"{prog}/{inner or 'unscoped'}"] += d
            if not inner:
                unscoped_ns[f"{prog}/{tracefile.op_kind(name)}/"
                            f"{tracefile.out_shape(name)}"
                            + ("" if op_name else " (no op name)")] += d
    sec = lambda ns: ns / 1e9 / n  # noqa: E731
    by_family = collections.defaultdict(float)
    for name, ns in idle_by_span.items():
        by_family[FAMILY.get(name, "unnamed")] += sec(ns)
    longest = [{"seconds": g / 1e9, "at_s": (lo - t_lo) / 1e9, "device": di,
                "spans": {k or "unnamed": v / 1e9 for k, v in sorted(
                    named.items(), key=lambda kv: -kv[1])[:3]}}
               for g, lo, di, named in sorted(
                   gaps_all, key=lambda gap: -gap[0])[:10]]
    return {
        "devices": n, "window_s": window / 1e9, "idle_s": sec(idle_ns),
        # Leaf operations can overlap (async copies): op_s is their sum,
        # which the scope shares divide by, not the union tracefile calls
        # busy_s.
        "op_s": sec(busy_ns),
        "has_spans": bool(spans),
        "has_scopes": any(k for k in scope_ns),
        "idle_by_span": {k or "unnamed": sec(v)
                         for k, v in idle_by_span.items()},
        "idle_by_family": dict(by_family),
        "scope_s": {k or "unscoped": sec(v) for k, v in scope_ns.items()},
        "under_scope_s": {k: sec(v) for k, v in under.items()},
        "program_scope_s": {k: sec(v) for k, v in prog_scope_ns.items()},
        "unscoped_top": [[k, sec(v)] for k, v in sorted(
            unscoped_ns.items(), key=lambda kv: -kv[1])[:8]],
        "longest_gaps": longest,
    }


def read_capture(path: str):
    """(devices, spans, t_lo, t_hi) of a trace file; the window as
    tracefile.read_xplane takes it."""
    from benchlib import xspace

    devices, threads = [], []
    t_lo, t_hi = float("inf"), float("-inf")
    for plane in xspace.read(tracefile.find_xplane(path)):
        if plane["name"].startswith("/host:CPU"):
            for line in plane["lines"]:
                ours = [(n, s, s + d) for n, s, d, _, _ in line["events"]
                        if n in FAMILY]
                if any(sp[0] in _LOOP_SPANS for sp in ours):
                    threads.append(ours)
            continue
        if not plane["name"].startswith("/device:TPU"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        for events in lines.values():
            if events:
                t_lo = min(t_lo, min(e[1] for e in events))
                t_hi = max(t_hi, max(e[1] + e[2] for e in events))
        if lines.get("XLA Ops"):
            ops = lines["XLA Ops"]
            devices.append({
                "ops": [e[:3] for e in ops],
                "op_names": [str(e[4].get("tf_op") or "") for e in ops],
                "modules": [e[:3] for e in lines.get("XLA Modules", [])]})
    # One loop thread a process; were there more, the busiest names most.
    spans = max(threads, key=len) if threads else []
    return devices, spans, t_lo, t_hi


def print_tables(red: dict, decode_chunk=None) -> None:
    """Beside the metrics: what PERF.md section 5 is written from."""
    w = red["window_s"] or 1.0
    say = lambda msg: print(f"hostspans: {msg}", flush=True)  # noqa: E731
    say(f"window {red['window_s']:.4f} s on {red['devices']} device(s); "
        f"idle {100 * red['idle_s'] / w:.3f} % of it")
    say("idle by family, % of the window: " + json.dumps(
        {k: round(100 * v / w, 3) for k, v in sorted(
            red["idle_by_family"].items(), key=lambda kv: -kv[1])}))
    say("idle by covering span, % of the window: " + json.dumps(
        {k: round(100 * v / w, 3) for k, v in sorted(
            red["idle_by_span"].items(), key=lambda kv: -kv[1])}))
    op = red["op_s"] or 1.0
    say("device time by scope, % of operation time: " + json.dumps(
        {k: round(100 * v / op, 2) for k, v in sorted(
            red["scope_s"].items(), key=lambda kv: -kv[1])}))
    say("device time by program x scope, % of operation time: " + json.dumps(
        {k: round(100 * v / op, 2) for k, v in sorted(
            red["program_scope_s"].items(), key=lambda kv: -kv[1])[:24]}))
    say("unscoped, largest, % of operation time: " + json.dumps(
        {k: round(100 * v / op, 2) for k, v in red["unscoped_top"]}))
    for g in red["longest_gaps"]:
        say(f"gap {g['seconds'] * 1e3:8.3f} ms at {g['at_s']:.4f} s "
            f"device {g['device']}: " + json.dumps(
                {k: round(v * 1e3, 3) for k, v in g["spans"].items()}))


def main(argv=None) -> int:
    src, dest = (argv or sys.argv[1:])[:2]
    devices, spans, t_lo, t_hi = read_capture(src)
    if not devices:
        print("hostspans: no device plane with operations in the trace",
              flush=True)
        return 1
    red = reduce_capture(devices, spans, t_lo, t_hi)
    print_tables(red)
    with open(dest, "w") as f:
        json.dump(red, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
