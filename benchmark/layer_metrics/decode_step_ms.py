"""Mean host time of one decode step: serve_decode_dispatch_seconds delta sum over (delta count x decode_chunk steps a dispatch)."""

LAYER = "model step, decode (engine -> transformer.forward)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import prom

    return prom.mean_ms(ctx.get("counters") or {},
                        "serve_decode_dispatch_seconds",
                        per=ctx.get("decode_chunk", 1))
