"""Device idle while the jitted call was being made or its result pulled (spans *.dispatch, *.sync, verify): launch latency and the way back, % of the traced window; the idle_* shares of a run add up to its device_idle_share."""

LAYER = "model step, host side (engine.step)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import spanread

    return spanread.idle_share(ctx, "runtime")
