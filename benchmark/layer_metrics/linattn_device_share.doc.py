"""Leaf device-operation time under the program's `linattn.*` scopes (the
linear-attention token mixer: projections, short convolution, the delta
rule, output norm and gate) over all operation time of the traced window,
every program of it. It is the part of `attn_device_share.doc` that the
full-attention layers do not take."""

LAYER = "model (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import linattn

    red = linattn.reduction(ctx)
    if not red:
        return None
    return 100.0 * sum(red["scope_s"].values()) / red["op_s"]
