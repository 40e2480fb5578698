"""How much more the window layers' flash forward computes in prefill than
a window needs: the scores in the (query block, kv block) pairs it visited
(`serve_window_scores_visited_total`: the blocks `block_ranges` gives for
the prompts of the measured window, times a block's area) over the scores
a window lets the same tokens see (`serve_window_scores_needed_total`:
min(t + 1, W) at position t). 1 = no masked score is computed; the key
block's size against the window decides it. Both are counted on the host,
a head and window layer, from the positions of each prefill dispatch."""

LAYER = "kernels (ops/flash_attention.py)"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(ctx):
    c = ctx.get("counters") or {}
    visited = c.get("serve_window_scores_visited_total", 0.0)
    needed = c.get("serve_window_scores_needed_total", 0.0)
    if not visited or not needed:
        return None
    blocks = c.get("serve_window_blocks_visited_total", 0.0)
    grid = c.get("serve_window_blocks_grid_total", 0.0)
    if grid and "_swa_counters_said" not in ctx:
        ctx["_swa_counters_said"] = True
        print(f"bench: swa: window layers' flash blocks visited / grid "
              f"{blocks / grid:.4f} of {grid:.0f}; scores visited "
              f"{visited:.0f} needed {needed:.0f}", flush=True)
    return visited / needed
