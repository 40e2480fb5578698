"""Leaf device-operation time under the program's `bsa.*` scopes (a
sparse-read layer's compressed keys, the choice of blocks, the attention
over the choice, the output gate) over all operation time of the traced
window, every program of it. The projections and the cache write of such
a layer stay under `attn.qkv`, `attn.kv_write`, `attn.out`."""

LAYER = "model (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import scopefamily

    found = scopefamily.family_seconds(ctx, "bsa")
    return None if not found else 100.0 * found[0] / found[1]
