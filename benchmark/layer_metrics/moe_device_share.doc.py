"""Leaf device-operation time under the program's `moe.*` scopes (the
sparse FFN: router, sort, the experts' grouped product, the shared expert,
the weighted way back) over all operation time of the traced window, every
program of it. It is the part of `ffn_device_share.doc` that the leading
dense layer does not take."""

LAYER = "model (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import sparse

    got = sparse.family_seconds(ctx, "moe")
    return None if got is None else 100.0 * got[0] / got[1]
