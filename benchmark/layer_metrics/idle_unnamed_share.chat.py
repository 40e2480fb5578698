"""Device idle under no span of the program: the blind spot of the idle_* shares, % of the traced window."""

LAYER = "device"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import spanread

    return spanread.idle_share(ctx, "unnamed")
