"""Seeded SL005 violations: host numpy, a Python bool() on a traced value,
and print() inside a jit-traced body; a host span, a profiler annotation
and a host clock in a rule reachable from run_sim (each would run once, at
trace time). The named scope is device op metadata, not a violation."""
import time

import jax
import numpy as np

from repro.core import spans


def _static_trace_key(platform, config, J, cap):
    return (J, cap)


def accrue_energy(s, const, cfg):
    total = np.sum(s.energy)
    if bool(s.truncated):
        print("truncated", total)
    return s


@jax.named_scope("process_batch")
def process_batch(s, const, cfg):
    t0 = time.perf_counter()
    with spans.span("process_batch"):
        with jax.profiler.TraceAnnotation("batch"):
            s = accrue_energy(s, const, cfg)
    return s._replace(t=s.t + 0 * t0)


def run_sim(s, const, cfg):
    with jax.named_scope("loop"):
        return process_batch(s, const, cfg)
