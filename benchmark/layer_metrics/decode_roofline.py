"""Least time of one decode step (weights and keys/values read once at the
chip's memory bandwidth, or its operations at the peak, whichever is
larger: memory at these batches) over the device time of one decode step
in the trace."""

LAYER = "model step, decode (engine -> transformer.forward)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import spec

    trace = ctx.get("trace")
    prog = (trace or {}).get("programs", {}).get("decode_fn")
    records = ctx.get("records")
    if not prog or not prog["launches"] or not records:
        return None
    step_s = prog["seconds"] / (prog["launches"] * ctx["decode_chunk"])
    kernel = spec.kernel("decode_step")
    slots = float(ctx["params"]["max_slots"])
    # A slot's context, on average over its life: prompt + half its output.
    mean_ctx = sum(r["prompt_tokens"] + r["max_tokens"] / 2.0
                   for r in records) / len(records)
    least, _bound = kernel.least_seconds(
        ctx["config"]["as_run"], slots, slots * mean_ctx, ctx["peaks"],
        chips=ctx["device"]["count"] if ctx["params"].get("mesh_tensor")
        else 1)
    return 100.0 * least / step_s
