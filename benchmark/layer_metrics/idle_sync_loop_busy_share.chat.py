"""Device idle under the spans `decode.sync` / `prefill.sync` during which the
event loop's thread was inside one of its spans (`api.submit`, `api.write`):
the worker may have waited for the interpreter lock. % of the traced window;
a part of idle_sync_ready_share + idle_sync_pull_share, not beside them."""

LAYER = "model step, host side (engine.step)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import syncspans

    return syncspans.sync_share(ctx, "loop_busy")
