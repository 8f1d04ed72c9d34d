"""Share of the chips' time spent waiting for the chip with the most loop
iterations: 1 - mean over chips of each chip's iterations / the largest
chip's. Only where a call is sharded over more than one chip."""


def read(ctx):
    if all(len(c.device_iters) < 2 for c in ctx.counters):
        return None
    mean = sum(float(c.device_iters.mean()) for c in ctx.counters)
    top = sum(float(c.device_iters.max()) for c in ctx.counters)
    return 100.0 * (1.0 - mean / top)
