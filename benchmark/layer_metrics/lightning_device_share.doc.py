"""Leaf device-operation time under the program's `lightning.*` scopes (the
lightning token mixer: projections with the QK norm and the rotary, the
recurrence, output norm, gate and projection) over all operation time of
the traced window, every program of it. It is the part of
`attn_device_share.doc` that the sparse-read layers do not take."""

LAYER = "model (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import scopefamily

    found = scopefamily.family_seconds(ctx, "lightning")
    return None if not found else 100.0 * found[0] / found[1]
