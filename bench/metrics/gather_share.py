"""Share of the window's engine calls, as the program's ``sweep`` spans
time them, spent gathering the lanes after the device loop: its
``sweep.gather`` spans (pad drop, per-lane slices and metrics). Without
them, nothing."""
import program_spans


def read(ctx):
    spans = program_spans.window(ctx)
    total = sum(s.t1 - s.t0 for s in spans if s.name == "sweep")
    if total <= 0:
        return None
    return 100.0 * sum(s.t1 - s.t0 for s in spans if s.name == "sweep.gather") / total
