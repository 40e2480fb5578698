"""Mean of the trainer own step time (dispatch plus device sync) over the steps that ended inside the window: step_s of its log lines at log_every 1."""

LAYER = "train step (train/step.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_tok_s"


def read(ctx):
    lines = [o for o in ctx.get("step_lines") or [] if "step_s" in o]
    if not lines:
        return None
    return sum(o["step_s"] for o in lines) / len(lines) * 1e3
