"""Prompt tokens the `prefill` spans whose `prefill.dispatch` began inside the
traced window say they carried (`tokens`: after the prefix, no padding) over
the device time of the `prefill_fn` modules in it: no client clock."""

LAYER = "model step, prefill (engine -> transformer.forward)"
UNIT = "tokens/s"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import syncspans

    return syncspans.prefill_tok_s(ctx)
