"""CI gate wrapper for `rbt check --strict`, with one JSON line for the
sweep table (docs/static-analysis.md).

Runs the full static audit — AST lint + abstract jaxpr program
contracts — and asserts the audit's own discipline on top of the
findings: ZERO XLA backend compiles (the program side is `make_jaxpr`
over ShapeDtypeStructs; a compile means real execution snuck in,
verified via the PR-7 compile sentinel) and a wall-time budget
(default 30 s on CPU — the audit must stay cheap enough to gate every
CI run). The printed value is the audit wall seconds, so a creeping
audit shows before it becomes a gate people skip.

Run: ``python tools/check_gate.py [budget_seconds]``
"""

from __future__ import annotations

import json
import sys

sys.path.insert(0, ".")  # repo-root invocation, like bench.py


def main() -> int:
    budget_s = float(sys.argv[1]) if len(sys.argv) > 1 else 30.0

    from runbooks_tpu.analysis.check import run_check

    report = run_check()
    for f in report.active:
        print(f.render())
    for s in report.stale:
        print(f"stale suppression: [{s.rule}] {s.path} ({s.reason})")
    rc = report.exit_code(strict=True)
    if not report.monitoring:
        # Without the monitoring feed the zero-compile assertion is
        # vacuous — fail rather than silently stop verifying (the same
        # review fix the PR-7 bench gate needed).
        print("check_gate: jax.monitoring unavailable — cannot verify "
              "the audit performed zero backend compiles", file=sys.stderr)
        rc = rc or 4
    if report.seconds > budget_s:
        print(f"check_gate: audit took {report.seconds:.1f}s, over the "
              f"{budget_s:.0f}s budget", file=sys.stderr)
        rc = rc or 5
    programs = ((report.census or {}).get("programs", [])
                if report.census else [])
    print(json.dumps({
        "bench": "static-check",
        "value": round(report.seconds, 2),
        "unit": "s_wall",
        "active": len(report.active),
        "stale": len(report.stale),
        "programs": len(programs),
        "backend_compiles": report.compiles,
        "monitoring": report.monitoring,
        "budget_s": budget_s,
        # The sweep table convention: vs_baseline > 1 is good.
        "vs_baseline": round(budget_s / max(report.seconds, 1e-9), 2),
    }))
    return rc


if __name__ == "__main__":
    sys.exit(main())
