"""The program's own spans and phase scopes, for the per-layer readers
that read them (``loop_us``, ``stage_share``, ``gather_share``,
``sched_pass_share``).

The harness hands each reader what it measured (``ctx``) after the run and
records nothing of the program itself. So this module, imported as the
cell's readers are loaded, before the run starts, turns on the program's
span recorder (``repro.core.spans.record``) for the rest of the process
where the command traces the run (``--trace 1``) and the program has one;
an untraced run records nothing and takes the program's path as before.
A program without ``repro.core.spans`` records nothing, and the readers
find nothing.

* :func:`window` gives the recorded spans of the measured window. The
  window's engine calls are the ``sweep`` spans before the last one (the
  traced call), one a call, as many as the run's counters have calls.
* :func:`phase_s` gives the device seconds of the traced slice by the
  program's phase scope (``jax.named_scope``: :data:`PHASES`), the
  innermost one in each op's ``op_name``. The trace's ``.xplane.pb`` is the
  newest under the run's default work directory,
  ``out/bench/<workload>/trace``.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)

# the program's phase scopes in the event loop, by the names it gives them
PHASES = ("loop", "accrue_energy", "quiet_batch", "process_batch", "complete",
          "scheduler_pass", "start_jobs", "power_step", "event_horizon")
OP_NAME_STAT = "tf_op"  # the stat of an XLA op's event metadata: its op_name
_PHASE = re.compile(r"\b(%s)\b" % "|".join(PHASES))

_on = contextlib.ExitStack()
_record: Optional[list] = None  # the program's record while it is on
_trace_dir: Optional[str] = None  # where the traced slice's profile goes
_logged = False


def start(trace_dir: Optional[str]) -> bool:
    """Record the program's spans from now on, and read the traced slice's
    profile under ``trace_dir``; False where the program has no spans."""
    global _record, _trace_dir
    stop()
    src = os.path.join(CHECKOUT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        from repro.core import spans
    except ImportError:  # a program without spans
        return False
    import jax

    # the persistent cache's key leaves out op metadata by default, so a
    # traced run could load a program compiled from other sources, whose
    # ops carry no phase scopes or other ones
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    _record = _on.enter_context(spans.record())
    _trace_dir = trace_dir
    return True


def stop() -> None:
    global _record, _trace_dir, _logged
    _on.close()
    _record, _trace_dir, _logged = None, None, False


def start_from_argv(argv: List[str]) -> bool:
    """:func:`start` where ``argv`` (``bench/run.py``'s options) traces the
    run, with the trace directory of its ``--workload``."""
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--workload")
    ap.add_argument("--trace", type=int, default=0)
    args, _ = ap.parse_known_args(argv)
    if args.trace != 1 or not args.workload:
        return False
    return start(os.path.join(CHECKOUT, "out", "bench", args.workload, "trace"))


def window(ctx, log=lambda msg: print(msg, file=sys.stderr, flush=True)) -> list:
    """The recorded spans of the window's engine calls, or [] where they
    cannot be told apart."""
    global _logged
    n = len(ctx.counters)
    sweeps = [s for s in _record or () if s.name == "sweep" and s.parent is None]
    if not n or len(sweeps) < n + 1:
        return []
    lo, hi = sweeps[-n - 1].t0, sweeps[-2].t1
    out = [s for s in _record if lo <= s.t0 and s.t1 <= hi]
    if not _logged:
        _logged = True
        total = lambda keep: sum(s.t1 - s.t0 for s in out if keep(s.name))
        log(f"[bench] program spans in the window: engine "
            f"{sum(c.engine_s for c in ctx.counters)} s, sweep "
            f"{total(lambda x: x == 'sweep')} s, sweep.* "
            f"{total(lambda x: x.startswith('sweep.'))} s")
    return out


def phase(op_name: str) -> Optional[str]:
    """The innermost of :data:`PHASES` in an op's ``op_name`` scope path."""
    found = _PHASE.findall(op_name)
    return found[-1] if found else None


def phase_s(ctx, path: Optional[str] = None) -> Dict[str, float]:
    """Device seconds of the traced slice (``ctx.trace.op_s``, summed over
    devices) by the phase of each op, from the trace at ``path``, else the
    newest one under the run's trace directory; {} where there is none."""
    if ctx.trace is None or not ctx.trace.op_s:
        return {}
    if path is None:
        files = glob.glob(os.path.join(_trace_dir, "**", "*.xplane.pb"),
                          recursive=True) if _trace_dir else []
        if not files:
            return {}
        path = max(files, key=os.path.getmtime)
    phases = {n: p for n, op in op_names(path).items()
              if (p := phase(op)) is not None}
    out: Dict[str, float] = {}
    for n, s in ctx.trace.op_s.items():
        if n in phases:
            out[phases[n]] = out.get(phases[n], 0.0) + s
    return out


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, i: int = 0, j: Optional[int] = None):
    """(field number, value) of each field of the protobuf message
    ``buf[i:j]``; a length-delimited value is its (start, end) in ``buf``."""
    j = len(buf) if j is None else j
    while i < j:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):  # fixed64, fixed32
            n = 8 if wire == 1 else 4
            v, i = (i, i + n), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_names(path: str) -> Dict[str, str]:
    """Each device op's ``op_name`` (the ``tf_op`` stat of its event
    metadata) by its trace name, read from the ``.xplane.pb`` file's
    ``/device:*`` planes. ``ProfileData`` gives an event's own stats but
    not its metadata's, so the few fields this needs are decoded here
    (``XSpace.planes`` 1; ``XPlane`` name 2, event_metadata 4,
    stat_metadata 5; ``XEventMetadata`` name 2, stats 5;
    ``XStatMetadata`` id 1, name 2; ``XStat`` metadata_id 1, str_value 5;
    a map entry's key 1 and value 2)."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[str, str] = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for k, v in _fields(buf, *plane):
            if k == 2:
                name = _text(buf, v)
            elif k == 4:
                events.extend(val for key, val in _fields(buf, *v) if key == 2)
            elif k == 5:
                meta = dict(_fields(buf, *dict(_fields(buf, *v))[2]))
                stat_names[meta.get(1, 0)] = _text(buf, meta[2]) if 2 in meta else ""
        if not name.startswith("/device:"):
            continue
        for ev in events:
            op, tf_op = None, None
            for k, v in _fields(buf, *ev):
                if k == 2:
                    op = _text(buf, v)
                elif k == 5:
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1)) == OP_NAME_STAT and 5 in stat:
                        tf_op = _text(buf, stat[5])
            if op and tf_op:
                out[op] = tf_op
    return out


start_from_argv(sys.argv[1:])
