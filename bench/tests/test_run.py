"""The command line refuses to run without a TPU, and a run's last line has
the contract's keys, on every cell cut to CPU size."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import tiny
from cells import BENCH, load_cell
from harness import run_cell

ROOT = os.path.dirname(BENCH)
CELLS = ["nasa_ipsc.grid", "nasa_ipsc.grid4"]


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _cli(ROOT, "--workload", "nasa_ipsc.grid", "--seed", str(2**31 + 9),
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path), "--workload", "nasa_ipsc.grid", "--seed", "3",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_result_line_keys(tiny_bench, tmp_path, name, trace):
    cell = load_cell(name, bench=tiny_bench)
    if name == "nasa_ipsc.grid4" and trace:
        assert "chip_wait_share" in cell.readers
    r = run_cell(cell, 2**31 + 77, 0.3, bool(trace), time.perf_counter(),
                 require_chip=False, workdir=str(tmp_path / "w"), log=lambda m: None)
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    assert list(r) == keys + (["breakdown"] if trace else []) + ["checks"]
    json.dumps(r)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0
    lanes = len(cell.traffic["schedulers"]) * len(cell.traffic["timeouts"])
    assert r["attempted"] % (lanes * cell.traffic["segments"]) == 0  # whole rounds
    assert set(r["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        assert set(r["metrics"]) <= {m["name"] for m in cell.per_layer}
        assert {"host_share", "batch_us", "batches_per_job"} <= set(r["metrics"])
        assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
        assert set(r["device"]) >= {"busy_s", "window_s"}
    else:
        assert set(r["metrics"]) == {"sim_jobs_per_s", "setup_s"}
        assert r["metrics"]["sim_jobs_per_s"]["value"] > 0
    for c in r["checks"].values():
        assert set(c) == {"value", "limit"}


def test_every_seed_runs_the_same_pool_in_its_own_order():
    from harness import pool, rounds

    cfg = load_cell("nasa_ipsc.grid").config
    mix = load_cell("nasa_ipsc.grid").traffic
    a, b = pool(cfg, mix), pool(cfg, mix)
    assert len(a) == mix["segments"]
    assert all((x[k] == y[k]).all() for x, y in zip(a, b) for k in x)
    assert any((a[0][k] != a[1][k]).any() for k in ("res", "runtime"))
    orders = [next(rounds(s, 4)) for s in range(2**31, 2**31 + 8)]
    assert all(sorted(o) == [0, 1, 2, 3] for o in orders)
    assert len({tuple(o) for o in orders}) > 1
    r1, r2 = rounds(2**31 + 5, 4), rounds(2**31 + 5, 4)
    assert [next(r1) for _ in range(3)] == [next(r2) for _ in range(3)]
