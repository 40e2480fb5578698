"""Mean wait of a request handed to the engine worker until the worker's next
intake (engine.submit), i.e. behind the tick the worker was in; in a closed
loop also how late a freed slot is refilled: serve_pending_wait_seconds,
delta sum / delta count over the window."""

LAYER = "entry points (serve/api.py, train/trainer.py)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import prom

    return prom.mean_ms(ctx.get("counters") or {},
                        "serve_pending_wait_seconds")
