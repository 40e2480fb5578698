"""Device idle under no span of the program: the blind spot of the idle_* shares, % of the traced window."""

LAYER = "device"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_tok_s"


def read(ctx):
    from benchlib import spanread

    return spanread.idle_share(ctx, "unnamed")
