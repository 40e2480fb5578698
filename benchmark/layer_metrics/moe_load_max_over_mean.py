"""Load imbalance among the held experts as a dispatch sees it: the
assignments of the most loaded held expert, summed over the measured
window's dispatches and sparse layers
(`serve_moe_layer_peak_assignments_total`), over the mean a held expert
got in the same dispatches and layers (`serve_moe_expert_tokens_total`
summed over experts ÷ experts held). 1 = perfectly even; a dispatch waits
for its most loaded expert. A decode dispatch is a chunk of 8 steps.

(`prom.parse` sums a family over its label sets, so the per-expert family
gives the mean only; the peak is a family of its own, which the engine
sums where it has the per-expert counts.)"""

LAYER = "model (models/moe.py)"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import sparse

    c = sparse.counters(ctx)
    if not c:
        return None
    here = c.get("serve_moe_expert_tokens_total", 0.0)
    peak = c.get("serve_moe_layer_peak_assignments_total", 0.0)
    held = ctx["config"]["as_run"]["num_experts"]
    if not here or not peak:
        return None
    return peak / (here / held)
