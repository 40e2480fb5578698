"""Device idle under every other span of the trainer's loop (data_wait, step, step.sync, log), % of the traced window."""

LAYER = "train loop (train/trainer.py)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_tok_s"


def read(ctx):
    from benchlib import spanread

    return spanread.idle_share(ctx, "step_host")
