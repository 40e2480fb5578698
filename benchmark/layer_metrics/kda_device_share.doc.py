"""Leaf device-operation time under the program's `kda.*` scopes (the KDA
token mixer: projections, the low-rank gates, the short convolution, the
delta rule, output norm, gate and projection) over all operation time of
the traced window, every program of it. It is the part of
`attn_device_share.doc` that the latent-attention layers do not take."""

LAYER = "model (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import scopefamily

    found = scopefamily.family_seconds(ctx, "kda")
    return None if not found else 100.0 * found[0] / found[1]
