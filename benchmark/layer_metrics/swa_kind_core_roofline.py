"""`swa_core_roofline` for a model whose attention kinds differ in query
heads: a window layer's attention core (`swa.core`, models/transformer.py),
the least time the chip needs for it on the tokens the traced window
prefilled (the flash forward under a window) and decoded (the ring read at
KV-head width) — operations and bytes from shapes,
benchmark/kernels/window_attention.py — over the device time under
`swa.core`, both programs. The arithmetic is `swa_core_roofline`'s; the
shape is read BY KIND from the configuration's
`as_run.attention_kinds.sliding_attention` (layers, query and KV heads,
key and value widths, window), where that reader takes the model's one
`num_attention_heads` for the window layers' too. A configuration without
`attention_kinds` reads nothing.

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

    kind = ((ctx.get("config") or {}).get("as_run") or {}).get(
        "attention_kinds", {}).get("sliding_attention")
    if not kind:
        return None
    secs = scopefamily.scope_seconds(ctx, "swa", "core")
    if not secs:
        return None
    prefills, contexts = sparse.traced_tokens(ctx)
    if not prefills and not contexts:
        return None
    heads, kv = kind["num_attention_heads"], kind["num_key_value_heads"]
    d, dv, w = kind["head_dim"], kind["v_head_dim"], kind["sliding_window"]
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
    return 100.0 * kind["layers"] * least / secs
