"""The readers of the program's own spans and phase scopes: which recorded
spans make the window, each device op's phase from a recorded TPU trace,
and the four readers on every cell cut to CPU size."""
import os
import re
import shutil
import sys
import time
from types import SimpleNamespace

import pytest

import program_spans
import tiny
import tracing
from cells import BENCH, load_cell, load_reader
from harness import run_cell
from repro.core.spans import Span

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "grid_call.xplane.pb")
SPAN_METRICS = {"loop_us", "stage_share", "gather_share"}


@pytest.fixture(autouse=True)
def recorder_off():
    program_spans.stop()
    yield
    program_spans.stop()


def test_phase_is_the_innermost_scope_of_the_op_name():
    phase = program_spans.phase
    assert phase("jit(_lambda)/jit(main)/vmap(while)/body/process_batch/"
                 "scheduler_pass/while/body/cumsum") == "scheduler_pass"
    assert phase("jit(f)/while/body/cond/branch_1_fun/process_batch/"
                 "power_step/select_n") == "power_step"
    assert phase("jit(f)/while/body/event_horizon/reduce_min") == "event_horizon"
    assert phase("jit(f)/while/cond/reduce_and") is None
    assert phase("jit(f)/vmap(loop)/while/cond/reduce_and:") == "loop"
    assert phase("jit(f)/loop/while/body/accrue_energy/mul:") == "accrue_energy"
    assert phase("jit(f)/scheduler_pass/while_loop/add:") == "scheduler_pass"
    # a transform names the scope it wraps
    assert phase("jit(_lambda)/vmap(process_batch)/complete/and:") == "complete"
    assert phase("jit(_lambda)/vmap(process_batch)/add:") == "process_batch"


def test_op_names_are_the_metadata_tf_op():
    """A TPU v5e trace of one 40-job, 24-lane grid call, trimmed to the ops
    from 1 ms before ``sweep.dispatch`` to 4 ms into ``sweep.wait``, each
    op's metadata keeping its ``tf_op`` stat."""
    names = program_spans.op_names(FIXTURE)
    assert len(names) == 366  # of 530 ops: the rest carry no tf_op
    assert all(v.startswith("jit(") for v in names.values())
    ops = {n for evs in tracing.read_trace(FIXTURE).device_ops.values()
           for n, _, _ in evs}
    assert set(names) <= ops
    assert {program_spans.phase(v) for v in names.values()} - {None} == {
        "scheduler_pass", "power_step", "start_jobs", "event_horizon",
        "complete", "accrue_energy"}


def test_sched_pass_share_reads_the_phase_over_busy_time(tmp_path):
    ev = tracing.read_trace(FIXTURE)
    r = tracing.reduce_trace(ev, 0.0, 0.009, [], mark_host=0.0)
    ctx = SimpleNamespace(trace=r)
    phases = program_spans.phase_s(ctx, FIXTURE)
    assert phases["scheduler_pass"] == max(phases.values())
    assert sum(phases.values()) <= sum(r.op_s.values()) + 1e-12
    read = load_reader(BENCH, "sched_pass_share")
    assert read(ctx) is None  # no trace directory: nothing recorded
    trace_dir = tmp_path / "trace" / "plugins" / "profile" / "run"
    trace_dir.mkdir(parents=True)
    shutil.copy(FIXTURE, trace_dir / "host.xplane.pb")
    assert program_spans.start(str(tmp_path / "trace"))
    v = read(ctx)
    assert v == pytest.approx(100 * phases["scheduler_pass"] / r.busy_s["/device:TPU:0"])
    assert 0 < v <= 100
    empty = tracing.reduce_trace(tracing.Events({}, None), 0.0, 0.009, [], 0.0)
    assert read(SimpleNamespace(trace=empty)) is None
    assert read(SimpleNamespace(trace=None)) is None


def test_the_command_line_turns_recording_on_only_when_traced():
    base = ["--workload", "nasa_ipsc.grid", "--seed", str(2**31 + 5), "--seconds", "25"]
    assert not program_spans.start_from_argv(base + ["--trace", "0"])
    assert program_spans._record is None
    assert not program_spans.start_from_argv(["-p", "xdist", "--trace-x", "1"])
    assert program_spans._record is None
    assert program_spans.start_from_argv(base + ["--trace=1"])
    assert program_spans._record == []
    assert program_spans._trace_dir == os.path.join(
        os.path.dirname(BENCH), "out", "bench", "nasa_ipsc.grid", "trace")


def _call(rec, t0, dt, wait):
    """The spans one engine call records: ``sweep`` and its children."""
    i = len(rec)
    rec.append(Span("sweep", None, t0, t0 + dt))
    rec.append(Span("sweep.consts", i, t0, t0 + 0.1 * dt))
    rec.append(Span("sweep.wait", i, t0 + 0.1 * dt, t0 + (0.1 + wait) * dt))
    rec.append(Span("sweep.gather", i, t0 + (0.1 + wait) * dt, t0 + dt))


def test_window_is_the_calls_before_the_traced_one(monkeypatch):
    rec = []
    _call(rec, 0.0, 5.0, 0.1)  # warm-up
    for k in range(3):
        rec.append(Span("experiments.workload", None, 6.0 + k, 6.1 + k))
        _call(rec, 6.1 + k, 0.8, 0.5)
    _call(rec, 10.0, 1.0, 0.2)  # the traced call
    monkeypatch.setattr(program_spans, "_record", rec)
    ctx = SimpleNamespace(counters=[SimpleNamespace(engine_s=0.8, iterations=100)] * 3)
    logged = []
    w = program_spans.window(ctx, log=logged.append)
    assert [s.name for s in w if s.parent is None] == [
        "sweep", "experiments.workload", "sweep", "experiments.workload", "sweep"]
    assert len(logged) == 1 and "engine 2.4" in logged[0]
    assert load_reader(BENCH, "loop_us")(ctx) == pytest.approx(1e6 * 1.2 / 300)
    assert load_reader(BENCH, "stage_share")(ctx) == pytest.approx(10.0)
    assert load_reader(BENCH, "gather_share")(ctx) == pytest.approx(40.0)
    ctx.counters = ctx.counters * 2  # more calls than recorded: no window
    assert program_spans.window(ctx) == []
    assert load_reader(BENCH, "loop_us")(ctx) is None


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


def _cover(err):
    m = re.search(r"engine (\S+) s, sweep (\S+) s, sweep\.\* (\S+) s", err)
    return tuple(float(x) for x in m.groups())


@pytest.mark.parametrize("name", ["nasa_ipsc.grid", "nasa_ipsc.grid4"])
def test_traced_run_reports_the_span_metrics(tiny_bench, tmp_path, capsys, name):
    cell = load_cell(name, bench=tiny_bench)
    assert SPAN_METRICS <= set(cell.readers)
    work = tmp_path / "w"
    assert program_spans.start(str(work / "trace"))
    r = run_cell(cell, 2**31 + 79, 0.3, True, time.perf_counter(),
                 require_chip=False, workdir=str(work), log=lambda m: None)
    assert r["correct"] is True, r["checks"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert SPAN_METRICS <= set(m)
    assert m["loop_us"] > 0
    assert 0 < m["stage_share"] and 0 < m["gather_share"]
    assert m["stage_share"] + m["gather_share"] < 100
    engine_s, sweep_s, children_s = _cover(capsys.readouterr().err)
    assert sweep_s == pytest.approx(engine_s, rel=0.02)
    assert children_s == pytest.approx(sweep_s, rel=0.02)


def test_untraced_run_and_a_program_without_spans_report_none(
        tiny_bench, tmp_path, monkeypatch):
    """Untraced, nothing is recorded; a program without ``repro.core.spans``
    still runs traced, and the readers of its spans report nothing."""
    cell = load_cell("nasa_ipsc.grid", bench=tiny_bench)
    r = run_cell(cell, 2**31 + 80, 0.3, False, time.perf_counter(),
                 require_chip=False, workdir=str(tmp_path / "u"), log=lambda m: None)
    assert set(r["metrics"]) == {"sim_jobs_per_s", "setup_s"}
    assert program_spans._record is None
    import repro.core

    monkeypatch.delattr(repro.core, "spans")
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    assert not program_spans.start(str(tmp_path / "w" / "trace"))
    r = run_cell(cell, 2**31 + 78, 0.3, True, time.perf_counter(),
                 require_chip=False, workdir=str(tmp_path / "w"), log=lambda m: None)
    assert r["correct"] is True
    assert {"host_share", "batch_us"} <= set(r["metrics"])
    assert not (SPAN_METRICS | {"sched_pass_share"}) & set(r["metrics"])
