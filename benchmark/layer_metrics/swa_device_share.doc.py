"""Leaf device-operation time under the program's `swa.*` scopes (a window
layer's token mixer: projections and rotary, the core, the ring's write,
the output projection) over all operation time of the traced window,
every program of it. With the full layers' `attn.*` parts and the mask it
is `attn_device_share.doc`."""

LAYER = "model (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import scopefamily

    got = scopefamily.family_seconds(ctx, "swa")
    return None if got is None else 100.0 * got[0] / got[1]
