"""Mean host time of one prefill dispatch (it syncs): serve_prefill_dispatch_seconds, delta sum / delta count over the window."""

LAYER = "model step, prefill (engine -> transformer.forward)"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import prom

    return prom.mean_ms(ctx.get("counters") or {},
                        "serve_prefill_dispatch_seconds")
