"""Device idle under the spans `decode.sync` / `prefill.sync` while the host
waited in `jax.block_until_ready` (their child `*.sync.ready`): the device
still worked, or the runtime had not woken the thread. % of the traced
window; with idle_sync_pull_share it adds up to the idle under `*.sync`."""

LAYER = "model step, host side (engine.step)"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p90_ms"


def read(ctx):
    from benchlib import syncspans

    return syncspans.sync_share(ctx, "ready")
