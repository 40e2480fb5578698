"""Device idle while the host admitted requests (spans worker.intake and tick.admit outside any prefill), % of the traced window; the idle_* shares of a run add up to its device_idle_share."""

LAYER = "admission (engine._admit)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import spanread

    return spanread.idle_share(ctx, "admit")
