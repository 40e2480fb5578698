"""Device idle while the host built and placed a dispatch's operands (spans prefill.operands, decode.operands), % of the traced window; the idle_* shares of a run add up to its device_idle_share."""

LAYER = "model step, host side (engine.step)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import spanread

    return spanread.idle_share(ctx, "operands")
