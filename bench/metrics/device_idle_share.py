"""Share of the traced slice in which no operation ran on the device (the
mean over the chips used), from the profiler trace."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.busy_s or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s / t.window_s)
