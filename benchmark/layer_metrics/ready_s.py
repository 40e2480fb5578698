"""Process start of the run to the entry point being ready: the server answering GET / after its warm-up, the trainer having finished its first (compiling) step."""

LAYER = "entry points (serve/api.py, train/trainer.py)"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(ctx):
    p = ctx["parts"]
    keys = ("start_to_spawn_s", "spawn_to_weights_s", "weights_to_ready_s",
            "spawn_to_startup_line_s", "startup_line_to_first_step_s")
    return sum(p[k] for k in keys if p.get(k) is not None)
