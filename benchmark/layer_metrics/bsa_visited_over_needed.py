"""How much more the sparse core computes than its choices need: the score
pairs it computed (`serve_bsa_pairs_visited_total`: prefill, every (query
block, key chunk) the walk visits for the prompts of the measured window;
decode, the view's keys a live row and step) over the score pairs the
chosen sets require (`serve_bsa_pairs_needed_total`: a window, the initial
block and the chosen blocks a token, causally clipped). 1 = nothing is
computed beside the choice. Both are counted on the host, a query head and
sparse-read layer, from the positions of each dispatch, prefill and
decode together; the line beside the metrics gives each program's."""

LAYER = "kernels (ops/block_sparse_attention.py)"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(ctx):
    c = ctx.get("counters") or {}
    visited = c.get("serve_bsa_pairs_visited_total", 0.0)
    needed = c.get("serve_bsa_pairs_needed_total", 0.0)
    if not visited or not needed:
        return None
    if "_bsa_counters_said" not in ctx:
        ctx["_bsa_counters_said"] = True
        print(f"bench: bsa: score pairs visited {visited:.0f} needed "
              f"{needed:.0f} a query head and layer; blocks chosen "
              f"{c.get('serve_bsa_blocks_chosen_total', 0.0):.0f} a KV head",
              flush=True)
    return visited / needed
