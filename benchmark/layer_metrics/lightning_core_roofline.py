"""The lightning recurrence itself (`lightning.core`,
ops/lightning_attention.py): the least time the chip needs for it on the
tokens the traced window prefilled and decoded (operations and bytes from
shapes, benchmark/kernels/lightning_attention.py) over the device time
under `lightning.core`, both programs.

Counted is what was asked for, never what a program computed beside it:
prefill, the prompt tokens the `prefill` spans dispatched in the window say
they carried, one pass over the state a prompt; decode, one token and one
pass a generated token that arrived inside the window after its request's
first (a live row of a step) — never a bucket's padding or a parked row."""

LAYER = "kernels (ops/lightning_attention.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import salaread, scopefamily, spec

    secs = scopefamily.scope_seconds(ctx, "lightning", "core")
    work = salaread.traced_work(ctx) if secs else None
    if not work:
        return None
    a = ctx["config"]["as_run"]
    heads, d = a["lightning_nh"], a["lightning_head_dim"]
    kernel = spec.kernel("lightning_attention")
    decoded = len(work["contexts"])
    least = sum(kernel.least_seconds(tokens, passes, heads, d, d,
                                     ctx["peaks"])[0]
                for tokens, passes in (
                    (work["prompts"] * work["prompt_len"], work["prompts"]),
                    (decoded, decoded)))
    return 100.0 * salaread.layers_of(a, "lightning-attn") * least / secs
