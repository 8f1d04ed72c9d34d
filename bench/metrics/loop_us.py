"""Device loop time per iteration: the program's ``sweep.wait`` spans
(``PendingSweep.result`` blocking on the device loop of each engine call)
in the window, over the loop iterations of the calls' chips, the base
``batch_us`` divides by. Without ``sweep.wait`` spans, nothing."""
import program_spans


def read(ctx):
    wait = [s.t1 - s.t0 for s in program_spans.window(ctx) if s.name == "sweep.wait"]
    iters = sum(c.iterations for c in ctx.counters)
    if not wait or not iters:
        return None
    return 1e6 * sum(wait) / iters
