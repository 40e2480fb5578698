"""Leaf device-operation time under the program's `attn` scope over all operation time of the traced window, every program of it."""

LAYER = "model (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tok_s"


def read(ctx):
    from benchlib import spanread

    return spanread.scope_share(ctx, "attn")
