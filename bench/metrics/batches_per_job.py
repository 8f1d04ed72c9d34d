"""Event batches per simulated job: the sum of every lane's ``n_batches``
over lanes times jobs. Exact, and repeats for a seed."""


def read(ctx):
    jobs = sum(len(c.lane_batches) * c.jobs for c in ctx.counters)
    if not jobs:
        return None
    return sum(float(c.lane_batches.sum()) for c in ctx.counters) / jobs
