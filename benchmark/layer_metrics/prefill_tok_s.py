"""Prompt tokens prefilled inside the traced window over the device time of
the prefill programs in the trace."""

LAYER = "model step, prefill (engine -> transformer.forward)"
UNIT = "tokens/s"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import arith

    trace = ctx.get("trace")
    prog = (trace or {}).get("programs", {}).get("prefill_fn")
    reqs = arith.prefilled_in(ctx.get("all_records"), ctx.get("trace_window"))
    if not prog or not prog["seconds"] or not reqs:
        return None
    return sum(r["prompt_tokens"] for r in reqs) / prog["seconds"]
