"""Mean time from slot assignment to the hand-over of the request's first
token (other groups' prefills of the tick, operands, dispatch, sync,
activation): serve_first_token_seconds, delta sum / delta count over the
window."""

LAYER = "model step, host side (engine.step)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import prom

    return prom.mean_ms(ctx.get("counters") or {},
                        "serve_first_token_seconds")
