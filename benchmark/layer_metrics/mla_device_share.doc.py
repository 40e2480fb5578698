"""Leaf device-operation time under the program's `mla.*` scopes (latent
attention: the query and down projections, prefill's expansion, decode's
absorbed products, the core, the output projection) over all operation
time of the traced window, every program of it. With the cache write and
the mask it is `attn_device_share.doc`."""

LAYER = "model (models/transformer.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import sparse

    got = sparse.family_seconds(ctx, "mla")
    return None if got is None else 100.0 * got[0] / got[1]
