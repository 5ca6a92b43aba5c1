"""device_idle_pct (layer: device): the share of the traced window in which
no operation ran on the chip, averaged over the chips used:
100 * (1 - union of the device's operation intervals / window)."""
from chipbench import trace as tracing


def read(ctx):
    devs = [d for d in ctx.trace.devices if ctx.trace.ops[d]][:ctx.chips]
    if not devs or ctx.trace.window_s <= 0:
        return None
    busy = sum(tracing.busy_ns(ctx.trace, d) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / 1e9 / ctx.trace.window_s)
