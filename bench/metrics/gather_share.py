"""Share of the window's engine calls, as the program's ``sweep`` spans
time them, spent gathering the lanes after the device loop: its
``sweep.gather`` spans (one host transfer of the stacked final state, the
pad drop, and each lane's metrics from that host copy). Without them,
nothing."""
import program_spans


def read(ctx):
    spans = program_spans.window(ctx)
    total = sum(s.t1 - s.t0 for s in spans if s.name == "sweep")
    if total <= 0:
        return None
    return 100.0 * sum(s.t1 - s.t0 for s in spans if s.name == "sweep.gather") / total
