"""Engine time per iteration of the device loop (``engine.run_sim``'s
``while_loop``). A replay runs ``n_batches`` iterations; a grid runs, on
each chip, as many as its longest lane, and the chips run side by side."""


def read(ctx):
    iters = sum(c.iterations for c in ctx.counters)
    if not iters:
        return None
    return 1e6 * sum(c.engine_s for c in ctx.counters) / iters
