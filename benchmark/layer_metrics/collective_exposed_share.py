"""Share of the traced window in which a collective operation ran on a
device while no compute operation did (averaged over the chips)."""

LAYER = "collectives (GSPMD all-reduces, ops/collective_matmul.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "serve_tok_s"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("collective_s"):
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
