"""Leaf device-operation time under the program's `shortconv.*` scopes (the
gated short convolution of a `conv` layer, models/transformer.
_short_conv_block: `shortconv.in` the projection to B | C | X and B * X,
`shortconv.core` the depthwise causal convolution and the tail it keeps,
`shortconv.out` the C gate and the output projection) over all operation
time of the traced window, every program of it. It is the part of
`attn_device_share.doc` that the full-attention layers do not take. The
mixer is left to the compiler (no kernel of this repo's): there is no
roofline of it. A program without the scopes (a model without such layers,
a tree before PR 40) reads nothing."""

LAYER = "model (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import scopefamily

    got = scopefamily.family_seconds(ctx, "shortconv")
    return 100.0 * got[0] / got[1] if got else None
