"""Device idle under the spans `decode.sync` / `prefill.sync` while the host
copied the result (their child `*.sync.pull`, and what else such a span
holds): the transfer and its conversion. % of the traced window; with
idle_sync_ready_share it adds up to the idle under `*.sync`."""

LAYER = "model step, host side (engine.step)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import syncspans

    return syncspans.sync_share(ctx, "pull")
