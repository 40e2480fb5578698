"""Tracing and lowering of the warmed programs: their calls' wall time outside the compiler: seconds of the set-up phase warmup.trace (warmup_census.phases of /debug/programs)."""

LAYER = "warm-up (engine.warmup)"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    from benchlib import spanread

    return spanread.phase_seconds(ctx, "warmup.trace")
