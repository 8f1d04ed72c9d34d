"""The check fails a run whose timed path is broken underneath.

Each fault is planted in the program's engine, below the harness, and the
rest of a run is driven as on the chip (the look for a chip skipped) on
each cell cut to CPU size: a loop that returns its state unchanged, half of
a batch left out (half of a grid's lanes, the other half standing in), the
gather between chips left out (every chip answers with the first one's
lanes), and an answer altered where it is produced (one job's finish time,
in every lane)."""
import time
from collections import OrderedDict

import pytest

import tiny
from cells import load_cell
from harness import run_cell


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture
def engine(monkeypatch):
    from repro.core import engine

    # compiled programs are cached per static structure: start empty so a
    # planted fault is traced, and drop what it compiled afterwards
    monkeypatch.setattr(engine, "_SIM_FNS", OrderedDict())
    monkeypatch.setattr(engine, "_SWEEP_FNS", OrderedDict())
    return engine


def unchanged(engine, mp):
    mp.setattr(engine, "run_sim", lambda s, const, cfg, max_batches=None: s)


def altered(engine, mp):
    real = engine.run_sim

    def run_sim(s, const, cfg, max_batches=None):
        out = real(s, const, cfg, max_batches=max_batches)
        return out._replace(job_finish=out.job_finish.at[0].add(1))

    mp.setattr(engine, "run_sim", run_sim)


def half_batch(engine, mp):
    real_sweep = engine.sweep_async

    def sweep_async(platform, workload, scenarios, *a, **kw):
        scenarios = list(scenarios)
        k = len(scenarios) // 2
        kept = scenarios[:k] * 2 + scenarios[2 * k:]
        return real_sweep(platform, workload, kept, *a, **kw)

    mp.setattr(engine, "sweep_async", sweep_async)


def no_gather(engine, mp):
    import jax

    real = engine.sweep_async

    def sweep_async(*a, **kw):
        pending = real(*a, **kw)
        d = pending._devices or 1
        per = pending._out.n_batches.shape[0] // d
        pending._out = jax.tree_util.tree_map(
            lambda x: jax.numpy.concatenate([x[:per]] * d), pending._out)
        return pending

    mp.setattr(engine, "sweep_async", sweep_async)


FAULTS = {
    "nasa_ipsc.grid": [unchanged, half_batch, altered],
    "nasa_ipsc.grid4": [unchanged, half_batch, no_gather, altered],
}


@pytest.mark.parametrize("name,fault", [
    (c, f) for c, fs in FAULTS.items() for f in fs],
    ids=lambda x: getattr(x, "__name__", x))
def test_fault_is_not_correct(tiny_bench, tmp_path, engine, monkeypatch, name, fault):
    fault(engine, monkeypatch)
    r = run_cell(load_cell(name, bench=tiny_bench), 2**31 + 31, 0.2, False,
                 time.perf_counter(), require_chip=False,
                 workdir=str(tmp_path / "w"), log=lambda m: None)
    assert r["correct"] is False, r["checks"]
