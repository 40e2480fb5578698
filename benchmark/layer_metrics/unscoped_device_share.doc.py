"""Leaf device-operation time under none of the program's scopes over all operation time of the traced window."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import spanread

    return spanread.scope_share(ctx, "unscoped")
