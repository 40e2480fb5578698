"""The delta rule itself (`linattn.core`, ops/gated_delta.py): the least
time the chip needs for it on the tokens the traced window prefilled and
decoded (operations and bytes from shapes, benchmark/kernels/gated_delta.py)
over the device time under `linattn.core`, both programs.

Counted is what was asked for, never what a program computed beside it:
prefill, the prompt tokens of the requests whose first token fell inside
the window (as `prefill_tok_s` matches them), one pass over the state a
request; decode, one token and one pass a generated token that arrived
inside the window after its request's first (a live row of a step) —
never a bucket's padding, a padding row or a parked row."""

LAYER = "kernels (ops/gated_delta.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import arith, linattn, spec

    red = linattn.reduction(ctx)
    secs = (red or {}).get("scope_s", {}).get("linattn.core")
    window = ctx.get("trace_window")
    if not secs or not window:
        return None
    a = ctx["config"]["as_run"]
    period = a["layer_period"]
    layers = a["num_hidden_layers"] // len(period) \
        * period.count("linear_attention")
    heads, dk, dv = (a["linear_num_value_heads"], a["linear_key_head_dim"],
                     a["linear_value_head_dim"])
    prefills = arith.prefilled_in(ctx.get("all_records"), window)
    decoded = sum(1 for r in ctx.get("all_records") or []
                  for t in (r.get("token_times") or [])[1:]
                  if window[0] <= t < window[1])
    if not prefills and not decoded:
        return None
    kernel = spec.kernel("gated_delta")
    least = sum(kernel.least_seconds(tokens, passes, heads, dk, dv,
                                     ctx["peaks"])[0]
                for tokens, passes in (
                    (sum(r["prompt_tokens"] for r in prefills),
                     len(prefills)),
                    (decoded, decoded)))
    return 100.0 * layers * least / secs
