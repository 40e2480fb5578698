"""Device idle while the engine worker waited for work (span worker.idle): load, not slowness, % of the traced window; the idle_* shares of a run add up to its device_idle_share."""

LAYER = "entry points (serve/api.py, train/trainer.py)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import spanread

    return spanread.idle_share(ctx, "no_work")
