"""Device idle during the host's own bookkeeping (spans decode.replay, prefill.activate, worker.finish and what is left of tick, decode, prefill outside their children), % of the traced window; the idle_* shares of a run add up to its device_idle_share."""

LAYER = "model step, host side (engine.step)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import spanread

    return spanread.idle_share(ctx, "bookkeeping")
