"""A sparse-read layer's attention over its choice (`bsa.core`,
ops/block_sparse_attention.py): the least time the chip needs for it on the
tokens the traced window prefilled and decoded — the score pairs the
chosen sets require, causally clipped, and the keys and values they read
(benchmark/kernels/block_sparse_attention.py) — over the device time under
`bsa.core`, both programs.

Counted is what was asked for: prefill, for every prompt the `prefill`
spans dispatched in the window carried, the keys each of its tokens reads
(a window, the initial block, the chosen blocks; every key while the
candidates are few); decode, for every token generated inside it after its
request's first, the keys it reads at its position — never a block's
masked scores, a chunk the walk visits beside the choice, a bucket's
padding or a parked row. The compressed scores lie under `bsa.select`."""

LAYER = "kernels (ops/block_sparse_attention.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import salaread, scopefamily, spec

    secs = scopefamily.scope_seconds(ctx, "bsa", "core")
    work = salaread.traced_work(ctx) if secs else None
    if not work:
        return None
    a = ctx["config"]["as_run"]
    heads, kv, d = (a["num_attention_heads"], a["num_key_value_heads"],
                    a["head_dim"])
    sp = a["sparse_config"]
    k = spec.kernel("block_sparse_attention")
    n, prompts = work["prompt_len"], work["prompts"]
    least = 0.0
    if prompts:
        least += prompts * k.least_seconds(
            k.core_operations(k.prompt_pairs(n, sp), heads, d),
            k.prefill_bytes(n, heads, kv, d, sp), ctx["peaks"])[0]
    if work["contexts"]:
        pairs = sum(k.decode_pairs(c, sp) for c in work["contexts"])
        least += k.least_seconds(
            k.core_operations(pairs, heads, d),
            k.decode_bytes(pairs, work["contexts"], heads, kv, d, sp),
            ctx["peaks"])[0]
    return 100.0 * salaread.layers_of(a, "minicpm4") * least / secs
