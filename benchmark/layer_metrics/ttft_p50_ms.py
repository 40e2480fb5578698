"""Median time to first token, client clock, from the instant each request was due; over the requests due inside the window."""

LAYER = "entry points (serve/api.py, train/trainer.py)"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import arith

    records = ctx.get("records")
    return arith.ttft_ms(records, 50) if records else None
