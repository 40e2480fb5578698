"""From a profiler trace (.xplane.pb) to what the per-layer readers read.

Run as a script in a CPU-only child (`JAX_PLATFORMS=cpu`): JAX is used only
to parse the file. The pure part (`reduce_planes`) works on plain tuples so
that the CPU tests can check it without a trace.

What a TPU trace holds (looked at by hand, PR 23): one plane per device,
`/device:TPU:<n>`, with the lines `XLA Modules` (one event per program
launch, named `jit_<fn>(<hash>)`) and `XLA Ops` (one event per HLO
instruction, named by its whole text, `%fusion.277 = bf16[16,18176]{...}
fusion(...)`). `while` events enclose the events of their bodies, so only
leaf instructions count as work. A Mosaic kernel is a `custom-call` with
`custom_call_target="tpu_custom_call"`.

Names are made to mean the same thing on the next PR: XLA's serial numbers
and hashes are stripped and an operation is grouped by (jitted program,
operation kind, output shape): `decode_fn/fusion/bf16[16,18176]`,
`idle/before_decode_fn`.
"""

from __future__ import annotations

import bisect
import collections
import json
import os
import re
import sys

_ENCLOSING = ("while", "conditional", "call")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\.clone)?(?:\.\d+)* = ")
_SHAPE = re.compile(r"([a-z]+\d*\[[\d,]*\])")


def program_name(module_event: str) -> str:
    """`jit_decode_fn(7155025908650796737)` -> `decode_fn`."""
    name = re.sub(r"\(\d+\)$", "", module_event.strip())
    return name[4:] if name.startswith("jit_") else name


def op_kind(text: str) -> str:
    """Instruction kind without XLA's serial numbers: `%fusion.277 = ...`
    -> `fusion`; a bare name (`fusion.12`) works too."""
    m = _NAME.match(text)
    name = m.group(1) if m else text.split(" ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+|\.clone)+$", "", name)


def out_shape(text: str) -> str:
    """Output type of an instruction, layouts stripped. A tuple keeps its
    element types: `(bf16[1,71,512,64],f32[1,71,512,128])`."""
    _, sep, rest = text.partition(" = ")
    if not sep:
        return ""
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    if rest.startswith("("):
        depth, end = 0, 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                end = i
                break
        return "(" + ",".join(_SHAPE.findall(rest[:end + 1])) + ")"
    m = _SHAPE.match(rest)
    return m.group(1) if m else ""


def flash_kind(text: str):
    """Which flash-attention kernel a Mosaic custom call is, by its
    outputs: forward gives (out, lse), dq one tensor, dkv two tensors of
    the same shape. None for anything else."""
    if " custom-call(" not in text:
        return None
    if "custom_call_target=" in text \
            and 'custom_call_target="tpu_custom_call"' not in text:
        return None   # AllocateBuffer, ConcatBitcast and the like
    shape = out_shape(text)
    parts = _SHAPE.findall(shape)
    if len(parts) == 2 and parts[0].startswith("bf16") \
            and parts[1].startswith("f32"):
        return "flash_fwd"
    if len(parts) == 2 and parts[0] == parts[1]:
        return "flash_dkv"
    if len(parts) == 1 and shape.count(",") == 3:
        return "flash_dq"
    return None


def union(intervals):
    """Merged, sorted [start, end] intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def covered(merged, s, e) -> float:
    """Length of [s, e] covered by merged intervals."""
    starts = [m[0] for m in merged]
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < e:
        total += max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return total


def reduce_device(modules, ops, async_ops, t_lo, t_hi) -> dict:
    """One device. modules/ops/async_ops: (name, start_ns, dur_ns)."""
    modules = sorted(modules, key=lambda m: m[1])
    mod_starts = [m[1] for m in modules]

    def program_at(t):
        i = bisect.bisect_right(mod_starts, t) - 1
        if i >= 0 and t <= modules[i][1] + modules[i][2]:
            return program_name(modules[i][0])
        return "no_program"

    leaf = [(n, s, d) for n, s, d in ops if op_kind(n) not in _ENCLOSING]
    busy = union([(s, s + d) for _, s, d in leaf if d > 0])
    busy_ns = sum(e - s for s, e in busy)
    groups = collections.defaultdict(lambda: [0.0, 0])
    kernels = collections.defaultdict(lambda: [0.0, 0])
    compute, coll = [], []
    for n, s, d in leaf:
        kind = op_kind(n)
        prog = program_at(s)
        fk = flash_kind(n)
        if fk:
            key = (prog, fk, out_shape(n))
            kernels[key][0] += d
            kernels[key][1] += 1
            kind = fk
        g = groups[f"{prog}/{kind}/{out_shape(n)}"]
        g[0] += d
        g[1] += 1
        if _COLLECTIVE.match(kind):
            if not kind.endswith("-start"):
                coll.append((s, s + d))
        elif d > 0:
            compute.append((s, s + d))
    for n, s, d in async_ops:
        if _COLLECTIVE.match(op_kind(n)):
            coll.append((s, s + d))
    compute_u, coll_u = union(compute), union(coll)
    coll_ns = sum(e - s for s, e in coll_u)
    exposed_ns = sum((e - s) - covered(compute_u, s, e) for s, e in coll_u)
    gaps = collections.defaultdict(float)
    edges = [t_lo] + [x for iv in busy for x in iv] + [t_hi]
    for i in range(0, len(edges), 2):
        gap = edges[i + 1] - edges[i]
        if gap <= 0:
            continue
        nxt = edges[i + 1]
        name = ("idle/at_the_end_of_the_window" if i + 2 >= len(edges)
                else "idle/before_" + program_at(nxt + 1))
        gaps[name] += gap
    progs = collections.defaultdict(lambda: [0.0, 0])
    for n, s, d in modules:
        p = progs[program_name(n)]
        p[0] += d
        p[1] += 1
    return {"busy_ns": busy_ns, "groups": groups, "kernels": kernels,
            "gaps": gaps, "programs": progs, "collective_ns": coll_ns,
            "collective_exposed_ns": exposed_ns}


def reduce_planes(devices: list, t_lo: float, t_hi: float) -> dict:
    """devices: one dict per device plane with `modules`, `ops`,
    `async_ops`. Times in ns; [t_lo, t_hi] is the traced window. Seconds
    and counts are averaged over the devices."""
    n = len(devices)
    per = [reduce_device(d["modules"], d["ops"], d.get("async_ops", ()),
                         t_lo, t_hi) for d in devices]

    def merge(field):
        acc = collections.defaultdict(lambda: [0.0, 0.0])
        for r in per:
            for k, (sec, cnt) in r[field].items():
                acc[k][0] += sec / 1e9 / n
                acc[k][1] += cnt / n
        return acc

    groups, kernels, programs = (merge("groups"), merge("kernels"),
                                 merge("programs"))
    gaps = collections.defaultdict(float)
    for r in per:
        for k, v in r["gaps"].items():
            gaps[k] += v / 1e9 / n
    top = lambda d, val: [[k, val(v)] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -val(kv[1]))[:10]]
    return {
        "devices": n,
        "window_s": (t_hi - t_lo) / 1e9,
        "busy_s": sum(r["busy_ns"] for r in per) / 1e9 / n,
        "collective_s": sum(r["collective_ns"] for r in per) / 1e9 / n,
        "collective_exposed_s":
            sum(r["collective_exposed_ns"] for r in per) / 1e9 / n,
        "programs": {k: {"seconds": v[0], "launches": v[1]}
                     for k, v in programs.items()},
        "groups": {k: {"seconds": v[0], "count": v[1]}
                   for k, v in groups.items()},
        "kernels": [{"program": k[0], "kernel": k[1], "shape": k[2],
                     "seconds": v[0], "count": v[1]}
                    for k, v in kernels.items()],
        "breakdown": {"device_ops": top(groups, lambda v: v[0]),
                      "idle_gaps": top(gaps, lambda v: v)},
    }


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(os.path.join(d, f) for d, _, files in os.walk(path)
                   for f in files if f.endswith(".xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def read_xplane(path: str):
    """(devices, t_lo, t_hi) from a trace file. The traced window runs
    from the first to the last device event of any chip: the host's tracer
    goes on logging while the capture is stopped and written, so host
    events do not bound it (an idle device at the capture's very edges is
    what this cannot see)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    devices, t_lo, t_hi = [], float("inf"), float("-inf")
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU")
        lines = {}
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events]
            if events and is_dev:
                t_lo = min(t_lo, min(e[1] for e in events))
                t_hi = max(t_hi, max(e[1] + e[2] for e in events))
            lines[line.name] = events
        if is_dev and lines.get("XLA Ops"):
            devices.append({"modules": lines.get("XLA Modules", []),
                            "ops": lines["XLA Ops"],
                            "async_ops": lines.get("Async XLA Ops", [])})
    return devices, t_lo, t_hi


def main(argv=None) -> int:
    src, dest = (argv or sys.argv[1:])[:2]
    devices, t_lo, t_hi = read_xplane(src)
    if not devices:
        print("tracefile: no device plane with operations in the trace",
              flush=True)
        return 1
    with open(dest, "w") as f:
        json.dump(reduce_planes(devices, t_lo, t_hi), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
