"""Mean wait from submit to admission inside the server: serve_queue_wait_seconds, delta sum / delta count over the window."""

LAYER = "admission (engine._admit)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import prom

    return prom.mean_ms(ctx.get("counters") or {}, "serve_queue_wait_seconds")
