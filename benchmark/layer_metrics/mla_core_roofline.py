"""Latent attention's core (`mla.core`, models/transformer.py): the least
time the chip needs for it on the tokens the traced window prefilled
(expanded: the flash forward at key width 192, value width 128) and
decoded (absorbed: against the latent leaf) — operations and bytes from
shapes, benchmark/kernels/mla_attention.py — over the device time under
`mla.core`, both programs.

Counted is what was asked for: prefill, the causal pairs of the prompts
whose first token fell inside the window; decode, for every token
generated inside it after its request's first, the tokens its row had
cached — never a bucket's padding, the view's unwritten slots or a parked
row."""

LAYER = "kernels (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import sparse, spec

    red = sparse.reduction(ctx)
    secs = (red or {}).get("scope_s", {}).get("mla.core")
    if not secs:
        return None
    prefills, contexts = sparse.traced_tokens(ctx)
    if not prefills and not contexts:
        return None
    a = ctx["config"]["as_run"]
    layers, heads = a["num_hidden_layers"], a["num_attention_heads"]
    dq = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
    dv, rank, rope = a["v_head_dim"], a["kv_lora_rank"], a["qk_rope_head_dim"]
    k = spec.kernel("mla_attention")
    lens = [r["prompt_tokens"] for r in prefills]
    pairs = sum(n * (n + 1) // 2 for n in lens)
    cached = float(sum(contexts))
    least = (k.least_seconds(k.prefill_operations(pairs, heads, dq, dv),
                             k.prefill_bytes(sum(lens), heads, dq, dv),
                             ctx["peaks"])[0]
             + k.least_seconds(k.decode_operations(cached, heads, rank, rope),
                               k.decode_bytes(cached, rank, rope),
                               ctx["peaks"])[0])
    return 100.0 * layers * least / secs
