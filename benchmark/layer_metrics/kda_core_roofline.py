"""The delta rule with a decay a channel itself (`kda.core`, ops/kda.py):
the least time the chip needs for it on the tokens the traced window
prefilled and decoded (operations and bytes from shapes,
benchmark/kernels/kda.py) over the device time under `kda.core`, both
programs.

Counted is what was asked for, never what a program computed beside it:
prefill, the prompt tokens the `prefill` spans dispatched in the window say
they carried, one pass over the state a prompt; decode, one token and one
pass a generated token that arrived inside the window after its request's
first (a live row of a step) — never a bucket's padding or a parked row.
The layers are the configuration's `kda_layers` as run, the leading one
among them. On a program without the scope (the parent of PR 48): None."""

LAYER = "kernels (ops/kda.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import salaread, scopefamily, spec

    secs = scopefamily.scope_seconds(ctx, "kda", "core")
    work = salaread.traced_work(ctx) if secs else None
    if not work:
        return None
    lin = ctx["config"]["as_run"]["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    kernel = spec.kernel("kda")
    decoded = len(work["contexts"])
    least = sum(kernel.least_seconds(tokens, passes, heads, d, d,
                                     ctx["peaks"])[0]
                for tokens, passes in (
                    (work["prompts"] * work["prompt_len"], work["prompts"]),
                    (decoded, decoded)))
    return 100.0 * len(lin["kda_layers"]) * least / secs
