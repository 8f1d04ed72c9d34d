"""Share of the vmapped lanes' loop iterations spent waiting for the
longest lane on their chip: 1 - sum of lane n_batches / (lanes x the
iterations their chip ran). Only where a call runs more than one lane."""


def read(ctx):
    if all(len(c.lane_batches) < 2 for c in ctx.counters):
        return None
    useful = sum(float(c.lane_batches.sum()) for c in ctx.counters)
    ran = sum(float(c.device_iters[c.lane_device].sum()) for c in ctx.counters)
    return 100.0 * (1.0 - useful / ran)
