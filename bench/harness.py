"""One run of one cell: set-up, the measured window, an optional traced
slice, and the check against the reference.

A mix's segments are a fixed pool: ``segments`` traces drawn from the mix's
own ``pool_seed``, the same for every run. The run's seed sets the order in
which the window calls them and which calls and lanes the check compares.
The window runs whole rounds, each one call on every segment of the pool in
an order drawn from the seed, until ``seconds`` have passed, and divides all
the work by all the time: every run does the same work, whatever its seed.
Each call is an ``entry`` span; the engine call inside it (the engine
function the entry names, such as ``engine.sweep``, ended by
``block_until_ready``) is an ``engine`` span.
The engine's final states are kept on the device through the window and
read after it: loop iterations per lane, completions, and for a grid each
sampled lane's schedule.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import sys
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

import check
import tracing
from cells import CHECKOUT, Cell
from traffic import generate
from tracing import clock

DONE = 3  # job status of a finished job in the engine's state


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and (info["platform"] != "tpu" or info["count"] < chips):
        raise NoChip(f"JAX found {info['count']} {info['platform']} device(s) "
                     f"({info['kind']}); the cell needs {chips} TPU chip(s)")
    return info


@dataclasses.dataclass
class Call:
    segment: int
    out_dir: str
    t1: float = float("nan")
    engine_s: float = 0.0
    result: object = None
    engine_out: Optional[Dict[str, object]] = None  # device arrays, (lanes, ...)
    lanes: int = 1
    devices: int = 1


class Recorder:
    """Spans, the engine's outputs per call, and compile events."""

    def __init__(self):
        self.spans = tracing.Spans()
        self.call: Optional[Call] = None
        self.lowerings: List[float] = []  # host time of each program lowered

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings.append(clock())

    def engine(self, fn, keep):
        def wrapped(*args, **kwargs):
            import jax

            h = self.spans.open("engine")
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
            span = self.spans.close(h)
            if self.call is not None:
                self.call.engine_s += span.t1 - span.t0
                keep(self.call, out)
            return out

        return wrapped


@contextlib.contextmanager
def engine_spans(rec: Recorder, entry):
    """Wrap the engine function that the entry's calls go through
    (``entry.engine_call``) for the run's duration; ``entry.keep`` keeps
    what the check and the counters read from its output."""
    from repro.core import engine

    real = getattr(engine, entry.engine_call)
    setattr(engine, entry.engine_call, rec.engine(real, entry.keep))
    try:
        yield
    finally:
        setattr(engine, entry.engine_call, real)


def segment_seed(seed: int, k: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([seed % 2**64, k])


ORDER, CHECK = 2**32, 2**32 + 1  # the run seed's streams


def pool(config: dict, mix: dict) -> List[dict]:
    """The mix's segments: the same traces for every run seed."""
    n_jobs, seed = int(config["trace_jobs"]), int(mix["pool_seed"])
    return [generate.generate(config["trace"], n_jobs, segment_seed(seed, k))
            for k in range(int(mix["segments"]))]


def rounds(seed: int, n_segments: int):
    """The segments' order in each round of the window, drawn from ``seed``."""
    rng = np.random.default_rng(segment_seed(seed, ORDER))
    while True:
        yield [int(k) for k in rng.permutation(n_segments)]


def counters(calls: List[Call]) -> List[SimpleNamespace]:
    """Per-call loop counters read from the kept final states."""
    out = []
    for c in calls:
        nb = np.asarray(c.engine_out["n_batches"]).reshape(-1)
        status = np.asarray(c.engine_out["job_status"])
        K, D = c.lanes, c.devices
        per = (K + (-K) % D) // D
        dev = np.arange(K) // per
        per_dev = np.array([nb[dev == d].max(initial=0) for d in range(D)])
        out.append(SimpleNamespace(
            lane_batches=nb, lane_device=dev, device_iters=per_dev,
            iterations=int(per_dev.max()), jobs=int(status.shape[-1]),
            completed=int(np.count_nonzero(status == DONE)),
            engine_s=c.engine_s))
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start_process: float, require_chip: bool = True,
             workdir: Optional[str] = None, log=lambda msg: None) -> dict:
    """Run ``cell`` once and return the result line as a dict."""
    device = device_info(cell.chips, require_chip)
    import jax

    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    from repro.launch.compile_cache import use_compile_cache

    # the program's persistent cache (``$JAX_COMPILATION_CACHE_DIR``, else
    # ``<checkout>/.jax_cache``), holding every program however small
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    rec = Recorder()
    jax.monitoring.register_event_duration_secs_listener(rec.on_duration)

    workdir = workdir or os.path.join(CHECKOUT, "out", "bench", cell.name)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    cfg, mix = cell.config, cell.traffic
    n_jobs = int(cfg["trace_jobs"])
    max_procs = int(cfg["platform"]["nb_nodes"])
    segments = []
    for k, jobs in enumerate(pool(cfg, mix)):
        path = os.path.join(workdir, f"segment_{k}.swf")
        generate.write_swf(jobs, path, max_procs)
        segments.append((jobs, path))
    quick = os.path.join(workdir, "warmup.swf")
    generate.write_swf(generate.quick(n_jobs), quick, max_procs)

    entry = cell.entry(cfg, mix)
    calls: List[Call] = []

    def one_call(k: int, swf: str, out_dir: str) -> Call:
        c = Call(k, out_dir)
        rec.call = c
        h = rec.spans.open("entry")
        c.result = entry.call(swf, out_dir)
        c.t1 = rec.spans.close(h).t1
        rec.call = None
        return c

    with engine_spans(rec, entry):
        one_call(-1, quick, os.path.join(workdir, "warmup"))
        t0 = clock()
        setup_s = t0 - t_start_process
        log(f"[bench] {cell.name}: set-up {setup_s} s, window {seconds} s")
        for order in rounds(seed, len(segments)):
            for k in order:
                calls.append(one_call(k, segments[k][1], os.path.join(
                    workdir, f"call_{len(calls)}")))
            if clock() - t0 >= seconds:
                break
        t1 = calls[-1].t1
        lowered_in_window = sum(t0 <= t <= t1 for t in rec.lowerings)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()[: max(cell.chips, 1)])
        traced = None
        if trace:
            traced = traced_slice(rec, one_call, segments, workdir,
                                  float(mix["trace_seconds"]), log)

    cnt = counters(calls)
    done = sum(c.completed for c in cnt)
    attempted = sum(c.lanes for c in calls)
    failed = sum(int(np.count_nonzero(
        (np.asarray(c.engine_out["job_status"]) != DONE).any(axis=-1)))
        for c in calls)
    log(f"[bench] {len(calls)} calls in {t1 - t0} s, {done} jobs completed")

    ok, shown = check_calls(cell, seed, calls, segments, entry, log)

    device["memory_peak_bytes"] = int(peak)
    metrics: Dict[str, dict] = {}
    result = {"correct": ok, "attempted": attempted, "failed": failed}
    if not trace:
        values = {"sim_jobs_per_s": done / (t1 - t0), "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    else:
        ctx = SimpleNamespace(
            window_s=t1 - t0, counters=cnt, lowered_in_window=lowered_in_window,
            trace=traced, config=cfg, traffic=mix, device_kind=device["kind"])
        for m in cell.per_layer:
            v = cell.readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = traced.mean_busy_s
        device["window_s"] = traced.window_s
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = tracing.breakdown(traced)
    result["checks"] = shown
    return result


def traced_slice(rec, one_call, segments, workdir, cap_s, log):
    """One more call under the profiler, cut after ``cap_s`` seconds."""
    prof = tracing.Profile(os.path.join(workdir, "trace"), cap_s)
    k = len(rec.spans.spans)
    one_call(0, segments[0][1], os.path.join(workdir, "call_traced"))
    t = clock()
    path = prof.finish()
    t_read = clock()
    events = tracing.read_trace(path)
    t_reduce = clock()
    out = tracing.reduce_trace(events, prof.t0, prof.t1, rec.spans.spans[k:],
                               prof.mark_host)
    n_ops = sum(len(v) for v in events.device_ops.values())
    log(f"[bench] trace: {n_ops} device ops; written in {t_read - t} s after the "
        f"call, read in {t_reduce - t_read} s, reduced in {clock() - t_reduce} s")
    return out


def check_calls(cell, seed, calls, segments, entry, log):
    """Compare a seeded sample of the window's calls with the reference."""
    rng = np.random.default_rng(segment_seed(seed, CHECK))
    nums = check.Numbers()
    limits = cell.config["limits"]
    try:
        for i in check.sample_calls(len(calls), int(cell.traffic["check_calls"]), rng):
            c = calls[i]
            lanes = check.sample_lanes(entry.scenarios, c.devices, rng)
            got = entry.outputs(c.result, c.out_dir, c.engine_out, lanes)
            jobs = segments[c.segment][0]
            for lane in lanes:
                label, timeout = entry.scenarios[lane]
                ref = check.reference(cell.config, jobs, label, timeout)
                nums.add(*check.compare(got[lane], ref))
        ok, shown = check.verdict(nums, limits)
    except Exception as e:  # a crash of the check is a failed check
        log(f"[bench] check failed: {type(e).__name__}: {e}")
        ok, shown = False, check.verdict(nums, limits)[1]
    return ok, shown
