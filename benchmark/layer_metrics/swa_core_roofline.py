"""A window layer's attention core (`swa.core`, models/transformer.py): the
least time the chip needs for it on the tokens the traced window prefilled
(the flash forward at key width 192, value width 128, under a window and a
sink) and decoded (the ring read at KV-head width) — operations and bytes
from shapes, benchmark/kernels/window_attention.py — over the device time
under `swa.core`, both programs.

Counted is what was asked for: prefill, for every prompt whose first token
fell inside the window, the pairs a window lets a token see (min(t + 1,
W) at position t); decode, for every token generated inside it after its
request's first, the live keys of its row's ring (min(context, W)) —
never a bucket's padding, a block's masked scores, the ring's margin or a
parked row."""

LAYER = "kernels (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import scopefamily, sparse, spec

    secs = scopefamily.scope_seconds(ctx, "swa", "core")
    if not secs:
        return None
    prefills, contexts = sparse.traced_tokens(ctx)
    if not prefills and not contexts:
        return None
    a = ctx["config"]["as_run"]
    layers = sum(1 for kind in a["hybrid_layer_pattern"] if kind)
    heads, kv = a["num_attention_heads"], a["swa_num_key_value_heads"]
    d, dv, w = a["head_dim"], a["v_head_dim"], a["sliding_window"]
    k = spec.kernel("window_attention")
    lens = [r["prompt_tokens"] for r in prefills]
    pairs = sum(k.prefill_pairs(n, w) for n in lens)
    # A decoded token at context n (n cached before it) reads n + 1 keys
    # with its own, at most the window.
    live = float(sum(k.decode_live(n + 1, w) for n in contexts))
    least = (k.least_seconds(k.prefill_operations(pairs, heads, d, dv),
                             k.prefill_bytes(sum(lens), heads, kv, d, dv),
                             ctx["peaks"])[0]
             + k.least_seconds(k.decode_operations(live, heads, d, dv),
                               k.decode_bytes(live, kv, d, dv),
                               ctx["peaks"])[0])
    return 100.0 * layers * least / secs
