"""Mean time from the first token's hand-over on the worker's thread to the
first SSE write of a delta of the request on the event loop's (the way to the
loop, detokenise, encode, write): serve_first_write_seconds, delta sum /
delta count over the window."""

LAYER = "entry points (serve/api.py, train/trainer.py)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import prom

    return prom.mean_ms(ctx.get("counters") or {},
                        "serve_first_write_seconds")
