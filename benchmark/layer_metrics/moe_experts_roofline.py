"""The routed experts' grouped product (`moe.experts`, models/moe.py): the
least time the chip needs for the assignments the traced window asked for
(operations and bytes from shapes and from what the router chose,
benchmark/kernels/moe_experts.py) over the device time under
`moe.experts`, both programs.

Counted is what was asked for: the real tokens prefilled and decoded in
the traced window (`sparse.traced_tokens`) x experts a token x sparse
layers x the share of assignments held here (the server's counters over
the measured window: the routing's, not a quarter by assumption). Bytes
are the matrices of the (layer, expert) pairs HIT, once a forward: a
prefill's forwards and a decode step's forwards in the trace (launches,
x decode_chunk) x the pairs a forward of that program hits on average
(counters) — never all held experts a forward."""

LAYER = "kernels (models/moe.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    from benchlib import sparse, spanread, spec

    red = sparse.reduction(ctx)
    secs = (red or {}).get("scope_s", {}).get("moe.experts")
    c = sparse.counters(ctx)
    trace = ctx.get("trace") or {}
    if not secs or not c:
        return None
    prefills, contexts = sparse.traced_tokens(ctx)
    if not prefills and not contexts:
        return None
    a = ctx["config"]["as_run"]
    layers = a["num_hidden_layers"] - a["first_k_dense_replace"]
    # prom.parse sums a family over its labels: assignments = here +
    # elsewhere, expert_tokens = here.
    share = c.get("serve_moe_expert_tokens_total", 0.0) \
        / c["serve_moe_assignments_total"]
    per_token = a["num_experts_per_tok"] * layers * share
    # Pairs hit a forward, both programs together (the family is summed
    # over its `program` label): the traced forwards are weighted alike.
    calls = c.get("serve_moe_expert_calls_total", 0.0)
    hit_share = c.get("serve_moe_expert_hits_total", 0.0) / calls \
        if calls else 1.0
    progs = trace.get("programs", {})
    forwards = (progs.get("prefill_fn", {}).get("launches", 0)
                + progs.get("decode_fn", {}).get("launches", 0)
                * (spanread.decode_chunk(ctx) or 1))
    hits = forwards * layers * a["num_experts"] * hit_share
    kernel = spec.kernel("moe_experts")
    tokens = sum(r["prompt_tokens"] for r in prefills) + len(contexts)
    least, _ = kernel.least_seconds(
        tokens * per_token, hits, a["hidden_size"],
        a["moe_intermediate_size"], ctx["peaks"])
    return 100.0 * least / secs
