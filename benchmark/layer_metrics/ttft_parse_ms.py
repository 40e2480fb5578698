"""Mean time from the HTTP handler's entry to the hand-over to the engine
worker (JSON, tokenizer, stream set-up; the event loop's thread):
serve_request_parse_seconds, delta sum / delta count over the window."""

LAYER = "entry points (serve/api.py, train/trainer.py)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import prom

    return prom.mean_ms(ctx.get("counters") or {},
                        "serve_request_parse_seconds")
