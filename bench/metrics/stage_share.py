"""Share of the window's engine calls, as the program's ``sweep`` spans
time them, spent staging the grid before the device loop runs: its
``sweep.consts`` (per-scenario consts), ``sweep.stack``, ``sweep.init`` and
``sweep.dispatch`` spans. Without them, nothing."""
import program_spans

STAGES = ("sweep.consts", "sweep.stack", "sweep.init", "sweep.dispatch")


def read(ctx):
    spans = program_spans.window(ctx)
    total = sum(s.t1 - s.t0 for s in spans if s.name == "sweep")
    if total <= 0:
        return None
    return 100.0 * sum(s.t1 - s.t0 for s in spans if s.name in STAGES) / total
