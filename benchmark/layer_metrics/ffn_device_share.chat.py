"""Leaf device-operation time under the program's `ffn` scope over all operation time of the traced window, every program of it."""

LAYER = "model (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import spanread

    return spanread.scope_share(ctx, "ffn")
