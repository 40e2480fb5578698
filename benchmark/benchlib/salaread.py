"""What the readers of MiniCPM-SALA's five metrics (PR 45) share: the
traced window's work, counted from the program's own spans where it has
them.

Prefill: the `prefill` spans whose dispatch began inside the traced window
say how many prompt tokens they carried (`syncspans.reduction`: no client
clock). A dispatch of this cell carries one prompt, so (tokens, spans)
gives the prompts' count and their mean length; what a prompt needs is
reckoned at that mean (its pairs and bytes are within a part in a thousand
of linear in the length over this cell's 13 312 - 15 872 tokens).
Decode: the tokens that arrived inside the window after their request's
first, with the position each was decoded at (`sparse.traced_tokens`: the
client's clock, which is all a decoded token has). On a program without
the spans, outside a traced run or without a capture: None.
"""

from __future__ import annotations


def traced_work(ctx: dict):
    """{"prompts": n, "prompt_len": mean tokens, "contexts": [position of
    each decoded token]} of the traced window, or None."""
    from benchlib import sparse, syncspans

    red = syncspans.reduction(ctx)
    if not red:
        return None
    tokens, spans = red["prefill"] or (0, 0)
    _, contexts = sparse.traced_tokens(ctx)
    if not spans and not contexts:
        return None
    return {"prompts": spans,
            "prompt_len": int(round(tokens / spans)) if spans else 0,
            "contexts": contexts}


def layers_of(as_run: dict, kind: str) -> int:
    period = as_run["layer_period"]
    return as_run["num_hidden_layers"] // len(period) * period.count(kind)
