"""step_mfu (layer: step): the operations one training epoch requires
(`chipbench.work`) over the device time of one run of the train step, as a
share of the chips' bf16 peak. Unlike `mfu`, the host's time between steps
and the eval do not count: it bounds what the device does while the step
runs. The train step is the program that took the most device time in the
window."""
from chipbench import work


def read(ctx):
    devs = [d for d in ctx.trace.devices
            if ctx.trace.modules.get(d)][:ctx.chips]
    if not devs:
        return None
    total, runs = {}, {}
    for d in devs:
        for m in ctx.trace.modules[d]:
            total[m.module] = total.get(m.module, 0.0) + m.dur_ns
            runs[m.module] = runs.get(m.module, 0) + 1
    step = max(total, key=total.get)
    seconds = total[step] / runs[step] / 1e9
    flops = work.epoch_flops(ctx.config, ctx.nodes, ctx.edges)
    return 100.0 * flops / seconds / (ctx.chips * ctx.peaks["flops_bf16"])
