"""Programs the warm-up compiled (or took from the cache): warmup_census.compiles of /debug/programs."""

LAYER = "warm-up (engine.warmup)"
UNIT = "programs"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    census = ctx.get("census")
    return None if not census else census.get("compiles")
