"""What the host waited for inside `*.sync`, what the event loop's thread did
meanwhile, and what the `prefill` spans carried, for the readers of PR 36.

`hostspans.FAMILY` is a closed list, so there the idle between the device's
last operation and the host holding a dispatch's result is one family
(`runtime`) under one span (`decode.sync`, `prefill.sync`). Since PR 36 the
program splits that span, while a capture runs, into `<span>.ready`
(`jax.block_until_ready`: the device still works, or the runtime has not
woken the thread) and `<span>.pull` (the copy to the host), puts a span
`api.write` on the event loop's thread beside `api.submit`, and says on its
`prefill` span how many prompt `tokens` the dispatch carried. This reads the
same capture once more, as `scopefamily.py` does (`hostspans.read_capture`
for the devices and the window, `xspace.read` for the host threads; plain
Python, no JAX). The pure parts (`split_sync`, `prefill_span_tokens`) work
on plain tuples. On a program without these spans (the parent of PR 36, the
trainer), outside a traced run, or without a capture, every reader function
here returns None.
"""

from __future__ import annotations

import bisect
import json

from benchlib import hostspans, spanread, tracefile, xspace

READY, PULL = ".sync.ready", ".sync.pull"
# The spans of the event loop's thread (serve/api.py).
LOOP_SPANS = ("api.submit", "api.write")


def sync_kind(name: str) -> str:
    """`ready`, `pull` or '' for a span of the loop thread. What a `*.sync`
    span holds outside `.ready` is pulling too (the `.pull` child, a sparse
    model's counts that ride the same boundary)."""
    if name.endswith(READY):
        return "ready"
    if name.endswith((PULL, ".sync")):
        return "pull"
    return ""


def _parts(segments: list, starts: list, lo: float, hi: float):
    """(start, end, name) of each disjoint segment's part in [lo, hi]."""
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(segments) and segments[i][0] < hi:
        s, e, name = segments[i]
        if min(e, hi) > max(s, lo):
            yield max(s, lo), min(e, hi), name
        i += 1


def split_sync(gaps: list, worker: list, loop: list) -> dict:
    """gaps: a list a device of the idle (start_ns, end_ns) intervals of
    the window (`hostspans.device_gaps`). worker: (name, start_ns, end_ns)
    of the engine worker's thread, `*.sync.ready` / `.pull` among them.
    loop: the same of the event loop's thread. Seconds are means over the
    devices, as hostspans' are. `loop_busy_s` is the part of `ready_s +
    pull_s` during which the event loop's thread was inside a span."""
    n = max(len(gaps), 1)
    segments = hostspans.flatten(worker)
    seg_starts = [s[0] for s in segments]
    loop_segments = hostspans.flatten(loop)
    loop_starts = [s[0] for s in loop_segments]
    total = {"ready": 0.0, "pull": 0.0, "loop_busy": 0.0}
    found = []
    for di, dev_gaps in enumerate(gaps):
        for lo, hi in dev_gaps:
            by_child, by_loop = {}, {}
            for s, e, name in _parts(segments, seg_starts, lo, hi):
                kind = sync_kind(name)
                if not kind:
                    continue
                total[kind] += e - s
                by_child[name] = by_child.get(name, 0.0) + e - s
                for ls, le, lname in _parts(loop_segments, loop_starts,
                                            s, e):
                    total["loop_busy"] += le - ls
                    by_loop[lname] = by_loop.get(lname, 0.0) + le - ls
            if by_child:
                found.append((sum(by_child.values()), lo, di, by_child,
                              by_loop))
    found.sort(key=lambda g: -g[0])
    return {
        "devices": n,
        "has_children": any(name.endswith((READY, PULL))
                            for name, _, _ in worker),
        "ready_s": total["ready"] / 1e9 / n,
        "pull_s": total["pull"] / 1e9 / n,
        "loop_busy_s": total["loop_busy"] / 1e9 / n,
        "longest": [{"seconds": ns / 1e9, "at_ns": lo, "device": di,
                     "children": {k: v / 1e9 for k, v in by_child.items()},
                     "loop": {k: v / 1e9 for k, v in by_loop.items()}}
                    for ns, lo, di, by_child, by_loop in found[:10]]}


def prefill_span_tokens(prefills: list, dispatches: list, t_lo: float,
                        t_hi: float):
    """prefills: (start_ns, end_ns, tokens or None) of the `prefill` spans;
    dispatches: start_ns of the `prefill.dispatch` spans. (tokens, spans)
    of the prefills whose dispatch began inside [t_lo, t_hi]; None where no
    span says what it carried."""
    said = [p for p in prefills if p[2] is not None]
    if not said:
        return None
    starts = sorted(d for d in dispatches if t_lo <= d <= t_hi)
    tokens = count = 0
    for s, e, carried in said:
        i = bisect.bisect_left(starts, s)
        if i < len(starts) and starts[i] <= e:
            tokens += int(carried)
            count += 1
    return tokens, count


def read_capture(path: str):
    """{gaps, worker, loop, prefills, dispatches, t_lo, t_hi} of a trace
    file, or None without a device plane."""
    devices, _, t_lo, t_hi = hostspans.read_capture(path)
    if not devices:
        return None
    workers, loop = [], []
    for plane in xspace.read(tracefile.find_xplane(path)):
        if not plane["name"].startswith("/host:CPU"):
            continue
        for line in plane["lines"]:
            events = line["events"]
            if any(e[0] in hostspans._LOOP_SPANS for e in events):
                workers.append(events)
            elif any(e[0] in LOOP_SPANS for e in events):
                loop += [(n, s, s + d) for n, s, d, _, _ in events
                         if n in LOOP_SPANS]
    events = max(workers, key=len) if workers else []
    return {
        "gaps": [hostspans.device_gaps(dev["ops"], t_lo, t_hi)
                 for dev in devices],
        "worker": [(n, s, s + d) for n, s, d, _, _ in events
                   if n in hostspans.FAMILY or n.endswith((READY, PULL))],
        "loop": loop,
        "prefills": [(s, s + d, st.get("tokens"))
                     for n, s, d, st, _ in events if n == "prefill"],
        "dispatches": [s for n, s, _, _, _ in events
                       if n == "prefill.dispatch"],
        "t_lo": t_lo, "t_hi": t_hi}


def reduction(ctx: dict):
    """This run's capture, read once a run (kept in ctx) and printed beside
    the metrics: {window_s, sync: split_sync's, prefill: (tokens, spans) or
    None}; None where there is no capture to read."""
    if not ctx.get("trace") or not ctx.get("cell"):
        return None
    if "_syncspans" not in ctx:
        capture = spanread.find_capture(ctx["cell"])
        cap = read_capture(capture) if capture is not None else None
        red = None
        if cap is not None:
            red = {"window_s": (cap["t_hi"] - cap["t_lo"]) / 1e9,
                   "sync": split_sync(cap["gaps"], cap["worker"],
                                      cap["loop"]),
                   "prefill": prefill_span_tokens(
                       cap["prefills"], cap["dispatches"], cap["t_lo"],
                       cap["t_hi"])}
            print_tables(red, cap["t_lo"])
        ctx["_syncspans"] = red
    return ctx["_syncspans"]


def print_tables(red: dict, t_lo: float) -> None:
    """Beside the metrics: what PERF.md section 5 is written from."""
    say = lambda msg: print(f"bench: syncspans: {msg}", flush=True)  # noqa: E731
    sync, w = red["sync"], red["window_s"] or 1.0
    if sync["has_children"]:
        say("idle under *.sync, % of the window: " + json.dumps(
            {k: round(100 * sync[k + "_s"] / w, 3)
             for k in ("ready", "pull", "loop_busy")}))
        ms = lambda d: {k: round(v * 1e3, 3) for k, v in sorted(  # noqa: E731
            d.items(), key=lambda kv: -kv[1])}
        for g in sync["longest"]:
            say(f"gap {g['seconds'] * 1e3:8.3f} ms at "
                f"{(g['at_ns'] - t_lo) / 1e9:.4f} s device {g['device']}: "
                + json.dumps(ms(g["children"])) + " loop "
                + json.dumps(ms(g["loop"])))
    if red["prefill"]:
        say(f"prefill spans dispatched in the window: {red['prefill'][1]} "
            f"carrying {red['prefill'][0]} prompt tokens")


def sync_share(ctx: dict, which: str):
    """Device idle under `*.sync` that lay under the `ready` or the `pull`
    child, or during which the event loop's thread was inside a span
    (`loop_busy`), % of the traced window; None where the program has no
    such children."""
    red = reduction(ctx)
    if not red or not red["sync"]["has_children"] or not red["window_s"]:
        return None
    return 100.0 * red["sync"][which + "_s"] / red["window_s"]


def prefill_tok_s(ctx: dict):
    """Prompt tokens the `prefill` spans dispatched in the traced window
    say they carried, over the device seconds of the `prefill_fn` modules
    in it; None without either."""
    red = reduction(ctx)
    prog = (ctx.get("trace") or {}).get("programs", {}).get("prefill_fn")
    if not red or not red["prefill"] or not prog or not prog["seconds"]:
        return None
    return red["prefill"][0] / prog["seconds"]
