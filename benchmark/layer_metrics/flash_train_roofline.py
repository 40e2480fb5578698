"""Flash forward and both backward kernels inside the train step: the
least time the chip needs for the attention of the packed rows (unmasked
pairs inside each document; operations and bytes from shapes), summed over
the kernel calls of the traced steps, over the kernels' device time."""

LAYER = "kernels (ops/flash_attention.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tok_s"


def read(ctx):
    from benchlib import spec

    trace = ctx.get("trace")
    kernels = [k for k in (trace or {}).get("kernels", [])
               if k["program"] == "step_fn"]
    pairs_row = ctx.get("attn_pairs_per_row")
    if not kernels or not pairs_row:
        return None
    flash = spec.kernel("flash_attention")
    a, job = ctx["config"]["as_run"], ctx["traffic"]["job_params"]
    b, s = int(job["batch_size"]), int(job["seq_len"])
    least = 0.0
    for k in kernels:   # every call works on the whole batch of one layer
        t, _bound = flash.least_seconds(
            k["kernel"], pairs_row * b, b * s, b * s,
            a["num_attention_heads"], a["num_kv_heads"], a["head_dim"],
            ctx["peaks"])
        least += t * k["count"]
    return 100.0 * least / sum(k["seconds"] for k in kernels)
