"""Mean wait of the step loop for its next batch over the steps that ended inside the window: data_wait_s of the trainer log lines."""

LAYER = "input pipeline (train/data.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_tok_s"


def read(ctx):
    lines = [o for o in ctx.get("step_lines") or [] if "data_wait_s" in o]
    if not lines:
        return None
    return sum(o["data_wait_s"] for o in lines) / len(lines) * 1e3
