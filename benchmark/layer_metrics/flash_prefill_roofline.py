"""Flash forward kernels inside the prefill programs: the least time the
chip needs for the causal attention of the prompts prefilled in the traced
window (operations and bytes from shapes) over the kernels' device time."""

LAYER = "kernels (ops/flash_attention.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import arith, spec

    trace = ctx.get("trace")
    secs = sum(k["seconds"] for k in (trace or {}).get("kernels", [])
               if k["program"] == "prefill_fn" and k["kernel"] == "flash_fwd")
    reqs = arith.prefilled_in(ctx.get("all_records"), ctx.get("trace_window"))
    if not secs or not reqs:
        return None
    flash = spec.kernel("flash_attention")
    a = ctx["config"]["as_run"]
    chips = ctx["device"]["count"] if ctx["params"].get("mesh_tensor") else 1
    lens = [r["prompt_tokens"] for r in reqs]
    tokens = float(sum(lens)) * a["num_hidden_layers"]
    least, _bound = flash.least_seconds(
        "flash_fwd", flash.causal_pairs(lens) * a["num_hidden_layers"],
        tokens, tokens, a["num_attention_heads"] / chips,
        max(a["num_kv_heads"] / chips, 1), a["head_dim"], ctx["peaks"])
    return 100.0 * least / secs
