"""Time to first token as the server sees it, handler entry to the first SSE
write: mean serve_ttft_seconds (handler entry to the first token's hand-over;
its parts are ttft_parse_ms, ttft_pending_ms, queue_wait_ms,
ttft_first_token_ms) plus mean serve_first_write_seconds, over the window."""

LAYER = "entry points (serve/api.py, train/trainer.py)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import prom

    counters = ctx.get("counters") or {}
    ttft = prom.mean_ms(counters, "serve_ttft_seconds")
    write = prom.mean_ms(counters, "serve_first_write_seconds")
    return None if ttft is None or write is None else ttft + write
