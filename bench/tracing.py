"""Spans, a bounded profiler slice, and the reduction from trace to numbers.

The harness records its own host spans (``entry``: one call of the entry
point users run; ``engine``: the engine call inside it, ended by
``block_until_ready``) on the host clock, and writes each one into the
profiler's trace too. A traced run profiles one extra call, cut after a
fixed number of seconds, and :func:`reduce_trace` turns the trace into:

* busy seconds per device: the union of the intervals in which an
  operation ran on it, inside the traced slice. Control-flow operations
  (``while``, ``conditional``, ``call``) are left out: the trace gives
  them one event spanning their bodies, gaps between the body's
  operations included;
* device time per operation name;
* the device's idle gaps, each named by the innermost harness span the
  host was in at the gap's midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

clock = time.perf_counter

MARK = "bench.clock"  # host annotation that ties the host clock to the trace's
# an XLA op's trace name is its HLO text: "%name = shape opcode(operands)..."
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_CONTAINERS = ("while", "conditional", "call")


def opcode(name: str) -> str:
    m = _OPCODE.search(name, name.find(" = ") + 1)
    return m.group(1) if m else name.split(" ", 1)[0]


def short(name: str) -> str:
    """``%fusion.95 = pred[11200]... fusion(...)`` -> ``%fusion.95 fusion``."""
    if " = " not in name:
        return name
    return f"{name.split(' = ', 1)[0]} {opcode(name)}"


@dataclasses.dataclass
class Span:
    name: str
    t0: float  # host clock, seconds
    t1: float = float("nan")


class Spans:
    """Host spans kept in memory; each also goes to the profiler's trace."""

    def __init__(self):
        self.spans: List[Span] = []

    def open(self, name: str):
        import jax

        span = Span(name, clock())
        ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        ann.__enter__()
        return span, ann

    def close(self, handle) -> Span:
        span, ann = handle
        ann.__exit__(None, None, None)
        span.t1 = clock()
        self.spans.append(span)
        return span


class Profile:
    """One profiler session, stopped at the latest after ``cap_s`` seconds
    (from a timer thread, so that a long call is cut, not traced whole)."""

    def __init__(self, log_dir: str, cap_s: float):
        import jax

        self.log_dir = log_dir
        self._lock = threading.Lock()
        self._on = True
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        # the offset between the host clock and the trace's clock
        self.mark_host = clock()
        with jax.profiler.TraceAnnotation(MARK):
            pass
        self.t0 = self.mark_host
        self.t1: Optional[float] = None
        self._timer = threading.Timer(cap_s, self.stop)
        self._timer.start()

    def stop(self) -> None:
        import jax

        with self._lock:
            if not self._on:
                return
            self._on = False
            self.t1 = clock()
            jax.profiler.stop_trace()

    def finish(self) -> str:
        self._timer.cancel()
        self.stop()
        files = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {self.log_dir}")
        return max(files, key=os.path.getmtime)


@dataclasses.dataclass
class Events:
    """What the reduction reads from one trace: device op intervals per
    device (ns, trace clock), and the host marker's trace time."""

    device_ops: Dict[str, List[Tuple[str, float, float]]]  # dev -> (name, t0, t1)
    mark_ns: Optional[float]


def read_trace(path: str) -> Events:
    """Device ops of every ``/device:*`` plane's ``XLA Ops`` line, and the
    host marker, from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Tuple[str, float, float]]] = {}
    mark = None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                evs = ops.setdefault(plane.name, [])
                for e in line.events:
                    evs.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARK:
                        mark = e.start_ns
    return Events(ops, mark)


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: Dict[str, float]  # per device
    op_s: Dict[str, float]  # op name -> device seconds, summed over devices
    op_n: Dict[str, int]  # op name -> events, summed over devices
    idle_gaps: List[Tuple[str, float]]  # (host label, seconds) per gap

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s) if self.busy_s else 0.0


def reduce_trace(events: Events, w0: float, w1: float, spans: Sequence[Span],
                 mark_host: float) -> Reduced:
    """Reduce ``events`` to the traced slice [w0, w1] (host clock, seconds).

    Host times map to the trace's clock through the marker: a host time h
    is ``mark_ns + (h - mark_host) * 1e9``. Without the marker, or with no
    device op, the result is empty and every reader finds nothing.
    """
    if events.mark_ns is None or not events.device_ops:
        return Reduced(w1 - w0, {}, {}, {}, [])

    def to_ns(h: float) -> float:
        return events.mark_ns + (h - mark_host) * 1e9

    a, b = to_ns(w0), to_ns(w1)
    busy: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    op_n: Dict[str, int] = {}
    gaps: List[Tuple[str, float]] = []
    host = sorted(((to_ns(s.t0), to_ns(s.t1), s.name) for s in spans),
                  key=lambda x: (x[0], -x[1]))
    leaf = {n: opcode(n) not in _CONTAINERS
            for evs in events.device_ops.values() for n, _, _ in evs}
    for dev, evs in sorted(events.device_ops.items()):
        clipped = [(max(s, a), min(e, b), n) for n, s, e in evs
                   if e > a and s < b and leaf[n]]
        for s, e, n in clipped:
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
            op_n[n] = op_n.get(n, 0) + 1
        union = merge([(s, e) for s, e, _ in clipped])
        busy[dev] = sum(e - s for s, e in union) * 1e-9
        if dev != min(events.device_ops):
            continue  # gaps are named on the first device only
        edges = [a] + [x for iv in union for x in iv] + [b]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((label(host, (g0 + g1) / 2), (g1 - g0) * 1e-9))
    return Reduced((w1 - w0), busy, op_s, op_n, gaps)


def label(host: Sequence[Tuple[float, float, str]], t: float) -> str:
    """The innermost host span holding time ``t``, or ``harness``."""
    best = None
    for s, e, name in host:
        if s > t:
            break
        if e >= t and (best is None or s >= best[0]):
            best = (s, e, name)
    return best[2] if best else "harness"


def breakdown(r: Reduced, top: int = 10) -> dict:
    """The device ops that took most time, and the idle time by what the
    host was doing (label, count of gaps and the longest)."""
    ops = sorted(r.op_s.items(), key=lambda kv: -kv[1])[:top]
    ops = [(short(n), s) for n, s in ops]
    by: Dict[str, List[float]] = {}
    for name, s in r.idle_gaps:
        by.setdefault(name, []).append(s)
    gaps = sorted(
        ((f"{name}: {len(v)} gaps, longest {max(v)} s", sum(v))
         for name, v in by.items()),
        key=lambda kv: -kv[1],
    )[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
