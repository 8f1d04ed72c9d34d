"""Host spans (``core/spans.py``) at the engine and runner boundaries, and
the device phase scopes of the event loop.

A grid call records one ``sweep`` span with its six children in order, on
one device, on four (a subprocess with four virtual CPU devices) and once
per chunk of a streaming run; recording changes no result, and every
phase scope reaches the compiled program's op metadata.
"""
import math
import re
import textwrap
import threading

import jax
import numpy as np
import pytest

from conftest import run_subprocess
from repro import experiments
from repro.core import engine, spans
from repro.core.types import BasePolicy, EngineConfig, PSMVariant
from repro.workloads.generator import GeneratorConfig, generate_workload
from repro.workloads.platform import PlatformSpec

CHILDREN = ["sweep.consts", "sweep.stack", "sweep.init", "sweep.dispatch",
            "sweep.wait", "sweep.gather"]
PHASES = ["loop", "accrue_energy", "quiet_batch", "process_batch", "complete",
          "scheduler_pass", "start_jobs", "power_step", "event_horizon"]
SCENARIOS = ["EASY PSUS", "FCFS PSAS+IPM",
             {"scheduler": "EASY PSAS", "timeout": 60}, 600]


def _grid():
    plat = PlatformSpec(nb_nodes=16)
    wl = generate_workload(GeneratorConfig(n_jobs=30, nb_res=16, seed=3))
    return plat, wl, EngineConfig(base=BasePolicy.EASY, psm=PSMVariant.PSUS)


def check_sweep_spans(rec, first=0, parent=None):
    """``rec[first:first + 7]`` is one ``sweep`` span under ``parent`` and
    its six children, in order, inside it."""
    top, kids = rec[first], rec[first + 1: first + 7]
    assert (top.name, top.parent) == ("sweep", parent)
    assert [k.name for k in kids] == CHILDREN
    assert all(k.parent == first for k in kids)
    assert all(math.isfinite(s.t1) and s.t0 <= s.t1 for s in [top] + kids)
    assert top.t0 <= kids[0].t0 and kids[-1].t1 <= top.t1
    assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))


def test_nothing_is_kept_unless_recording():
    plat, wl, cfg = _grid()
    with spans.record() as rec:
        pass
    engine.sweep(plat, wl, SCENARIOS[:2], cfg)
    with spans.span("outside"):
        pass
    assert rec == []
    assert spans._record is None


def test_span_parents_follow_nesting_on_each_thread():
    with spans.record() as rec:
        with spans.span("a"):
            with spans.span("b"):
                pass
            t = threading.Thread(target=lambda: spans.begin("c"))
            t.start()
            t.join()
            with spans.span("d"):
                pass
    assert [(s.name, s.parent) for s in rec] == [
        ("a", None), ("b", 0), ("c", None), ("d", 0)]
    assert math.isnan(rec[2].t1)  # "c" never ended


@pytest.mark.parametrize("devices", [None, 1])
def test_sweep_records_its_six_phases(devices):
    plat, wl, cfg = _grid()
    with spans.record() as rec:
        with spans.span("outer"):
            batch = engine.sweep(plat, wl, SCENARIOS, cfg, devices=devices)
    assert len(batch.metrics) == len(SCENARIOS)
    assert len(rec) == 8 and rec[0].name == "outer"
    check_sweep_spans(rec, first=1, parent=0)
    assert rec[1].t1 <= rec[0].t1


def test_sweep_records_its_six_phases_on_four_devices():
    out = run_subprocess(textwrap.dedent("""
        import sys
        sys.path.insert(0, "tests")
        from test_spans import SCENARIOS, _grid, check_sweep_spans
        from repro.core import engine, spans
        plat, wl, cfg = _grid()
        with spans.record() as rec:
            batch = engine.sweep(plat, wl, SCENARIOS * 2 + [900], cfg, devices=4)
        assert batch.devices == 4 and len(batch.metrics) == 9
        assert len(rec) == 7, rec
        check_sweep_spans(rec)
        print("ok")
    """), n_devices=4)
    assert out.strip().endswith("ok")


def test_streaming_run_records_one_sweep_per_chunk(tmp_path):
    exp = experiments.Experiment(
        name="stream", workload={"preset": "fig3_small", "n_jobs": 30},
        platform=16, schedulers=("EASY PSUS", "FCFS PSAS"),
        timeouts=(60, 600, 1800), out=str(tmp_path / "out"))
    with spans.record() as rec:
        chunks = list(experiments.run(exp, stream=True, chunk_scenarios=4))
    assert [len(c) for c in chunks] == [4, 2]
    sweeps = [i for i, s in enumerate(rec) if s.name == "sweep"]
    assert len(sweeps) == 2
    for i in sweeps:
        kids = [s.name for s in rec if s.parent == i]
        assert kids == CHILDREN
        assert all(s.t0 <= rec[i].t1 for s in rec if s.parent == i)
    names = [s.name for s in rec]
    assert names.count("experiments.workload") == 1
    assert names.count("experiments.write") == 3  # after each chunk, then all
    # chunk 2 is dispatched before chunk 1 is gathered
    assert rec[sweeps[1]].t0 < rec[sweeps[0]].t1


def test_recording_leaves_final_states_bit_identical():
    plat, wl, cfg = _grid()
    off = engine.sweep(plat, wl, SCENARIOS, cfg)
    with spans.record() as rec:
        on = engine.sweep(plat, wl, SCENARIOS, cfg)
    assert [s.name for s in rec] == ["sweep"] + CHILDREN
    a, b = jax.tree_util.tree_leaves(off.states), jax.tree_util.tree_leaves(on.states)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert [m.row() for m in off.metrics] == [m.row() for m in on.metrics]


@pytest.fixture(scope="module")
def hlo():
    """The compiled HLO text of the sweep program (traced policy flags) and
    of a single statically specialized run (the only one with quiet
    batches, which traced flags turn off)."""
    plat, wl, cfg = _grid()
    cfg = engine.trim_window(cfg, len(wl))
    cap = engine.default_batch_cap(len(wl))
    base = engine.make_const(plat, cfg)
    consts = [engine._scenario_const(sc, base, plat, cfg)[0] for sc in SCENARIOS]
    stacked = jax.tree_util.tree_map(lambda *xs: jax.numpy.stack(xs), *consts)
    s0 = engine.init_state(plat, wl, cfg)
    sweep = engine._sweep_program(cfg, cap).lower(s0, stacked)
    single = engine.make_const(plat, cfg, specialize=True)
    one = jax.jit(lambda s: engine.run_sim(s, single, cfg, max_batches=cap))
    return {"sweep": sweep.compile().as_text(),
            "single": one.lower(s0).compile().as_text()}


@pytest.mark.parametrize("program,phase", [
    ("sweep", p) for p in PHASES if p != "quiet_batch"] + [
    ("single", p) for p in PHASES])
def test_compiled_program_carries_each_phase_scope(hlo, program, phase):
    # a transform names the outermost scope it wraps: ".../vmap(loop)/while"
    assert re.search(rf'op_name="[^"]*[/(]{phase}[/)]', hlo[program]), phase
