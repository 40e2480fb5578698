"""Leaf device-operation time under the program's per-head output gate
(`attn.gate` in full layers, `swa.gate` in window layers: the projection
to one number a query head, the sigmoid, and its product with the core's
output before the output projection; models/transformer.py) over all
operation time of the traced window, every program of it. Part of
`attn_device_share.doc`. A program without the scopes (a model without the
gate, a tree before PR 38) reads nothing."""

LAYER = "model (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import scopefamily

    secs, total = 0.0, None
    for family in ("attn", "swa"):
        red = scopefamily.reduction(ctx, family)
        if red:
            secs += red["scope_s"].get(family + ".gate", 0.0)
            total = red["op_s"]
    return 100.0 * secs / total if secs and total else None
