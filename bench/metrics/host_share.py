"""Share of the window in which no engine call ran: the entry point's own
host work (SWF parse, resolve, init_state, metrics, file writes) and the
harness's loop. From the harness's spans."""


def read(ctx):
    engine = sum(c.engine_s for c in ctx.counters)
    return 100.0 * (1.0 - engine / ctx.window_s)
