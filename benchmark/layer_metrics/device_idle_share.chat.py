"""Share of the traced window in which no operation ran on the device: 1 - union of device-op intervals / traced window, averaged over the chips used."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p90_ms"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
