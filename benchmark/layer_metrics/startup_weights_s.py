"""Mesh, load_model and placement until the weights are on the device: seconds of the set-up phase startup.weights (warmup_census.phases of /debug/programs)."""

LAYER = "entry points (serve/api.py, train/trainer.py)"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    from benchlib import spanread

    return spanread.phase_seconds(ctx, "startup.weights")
