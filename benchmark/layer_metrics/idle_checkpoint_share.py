"""Device idle while the trainer saved a checkpoint (span checkpoint), % of the traced window."""

LAYER = "train loop (train/trainer.py)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_tok_s"


def read(ctx):
    from benchlib import spanread

    return spanread.idle_share(ctx, "checkpoint")
