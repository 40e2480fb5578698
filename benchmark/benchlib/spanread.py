"""What the per-layer readers of PR 24 share: the reduction of a traced
run's capture by `hostspans.py` (run once, in a CPU-only child, written
beside the capture), and the set-up phases of `/debug/programs`.

A reader gets `ctx` and nothing else; `run.py` leaves the capture of a
traced run at `.bench_work/<cell>/artifacts/profiles/**/*.xplane.pb`.
Outside a traced run, or on a program that has no such spans, scopes or
phases (the parent of PR 24), every function here returns None.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchlib import spec

HOSTSPANS = os.path.join(spec.BENCH_DIR, "benchlib", "hostspans.py")


def find_capture(cell: str):
    """The newest .xplane.pb of the cell's work directory, or None."""
    root = os.path.join(spec.ROOT, ".bench_work", cell, "artifacts",
                        "profiles")
    found = [os.path.join(d, f) for d, _, files in os.walk(root)
             for f in files if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def reduction(ctx: dict):
    """hostspans' reduction of this run's capture; None outside a traced
    run or when the capture cannot be reduced."""
    if not ctx.get("trace") or not ctx.get("cell"):
        return None
    capture = find_capture(ctx["cell"])
    if capture is None:
        return None
    out = capture + ".hostspans.json"
    if not os.path.exists(out) \
            or os.path.getmtime(out) < os.path.getmtime(capture):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("BENCH_RUN", None)
        try:
            done = subprocess.run(
                [sys.executable, HOSTSPANS, capture, out], env=env,
                cwd=spec.ROOT, capture_output=True, text=True, timeout=600)
        except subprocess.TimeoutExpired:
            print("bench: hostspans timed out", flush=True)
            return None
        for line in done.stdout.splitlines():
            if line.startswith("hostspans:"):
                print("bench: " + line, flush=True)
        if done.returncode != 0 or not os.path.exists(out):
            print(f"bench: hostspans failed rc={done.returncode}:\n"
                  + done.stderr[-2000:], flush=True)
            return None
        chunk = decode_chunk(ctx)
        prog = ctx["trace"].get("programs", {}).get("decode_fn")
        if chunk and prog and prog.get("launches"):
            print("bench: hostspans: decode_fn device time a step "
                  f"{1e3 * prog['seconds'] / (prog['launches'] * chunk):.3f}"
                  f" ms (decode_chunk {chunk} as the server publishes it)",
                  flush=True)
    with open(out) as f:
        return json.load(f)


def decode_chunk(ctx: dict):
    """Decode steps a dispatch: what the server publishes in
    /debug/programs, else the harness's own constant."""
    return ((ctx.get("census") or {}).get("decode_chunk")
            or ctx.get("decode_chunk"))


def idle_share(ctx: dict, family: str):
    """Device idle time under the spans of one family, % of the traced
    window. None where the program has no spans in the capture."""
    red = reduction(ctx)
    if not red or not red.get("has_spans") or not red.get("window_s"):
        return None
    return 100.0 * red["idle_by_family"].get(family, 0.0) / red["window_s"]


def scope_share(ctx: dict, scope: str):
    """Leaf device-operation time under a scope of the program, % of all
    operation time of the window; `unscoped` is the time under none. None
    where the capture has neither scopes nor spans of this program (an
    executable from before the scopes reads 100 % unscoped, not
    nothing, under a program that has the spans)."""
    red = reduction(ctx)
    if not red or not red.get("op_s") \
            or not (red.get("has_scopes") or red.get("has_spans")):
        return None
    seconds = (red["scope_s"].get("unscoped", 0.0) if scope == "unscoped"
               else red["under_scope_s"].get(scope, 0.0))
    return 100.0 * seconds / red["op_s"]


def phase_seconds(ctx: dict, phase: str):
    """Seconds of one set-up phase (`warmup_census["phases"]`)."""
    return ((ctx.get("census") or {}).get("phases") or {}).get(phase)
