"""Every cell resolves its files by name, and a cell added as files alone
is found: also one on a partitioned machine of node groups, which the CPU
cut keeps and the check holds to its partitions."""
import dataclasses
import json
import os
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

import control
import tiny
from cells import BENCH, load_cell, load_reader
from harness import run_cell
from test_partition import group

ROOT = os.path.dirname(BENCH)
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_by_name(name):
    cell = load_cell(name)
    assert cell.config["platform"]["nb_nodes"] > 0
    assert callable(cell.entry)
    assert {m["name"] for m in cell.end_to_end} >= {"sim_jobs_per_s", "setup_s"}
    assert cell.per_layer and set(cell.readers) == {m["name"] for m in cell.per_layer}


def test_metric_restrictions_follow_workloads_key():
    names = lambda c: {m["name"] for m in load_cell(c).per_layer}  # noqa: E731
    assert "lane_idle_share" in names("nasa_ipsc.grid")
    assert "chip_wait_share" in names("nasa_ipsc.grid4")
    assert "chip_wait_share" not in names("nasa_ipsc.grid")


SWEEP = {"host_share", "compiles_in_window", "batch_us", "batches_per_job",
         "lane_idle_share", "device_idle_share", "loop_us", "stage_share",
         "gather_share"}


@pytest.mark.parametrize("name,metrics", [
    ("nasa_ipsc.grid", SWEEP | {"sched_pass_share"}),
    ("nasa_ipsc.grid4", SWEEP | {"chip_wait_share"}),
])
def test_each_cell_reports_the_per_layer_metrics_it_did(name, metrics):
    assert {m["name"] for m in load_cell(name).per_layer} == metrics


def test_lane_idle_share_reads_nothing_without_lanes():
    read = load_reader(BENCH, "lane_idle_share")
    one = SimpleNamespace(lane_batches=np.array([7.0]), lane_device=np.array([0]),
                          device_iters=np.array([7.0]))
    assert read(SimpleNamespace(counters=[one, one])) is None
    two = SimpleNamespace(lane_batches=np.array([4.0, 8.0]),
                          lane_device=np.array([0, 0]), device_iters=np.array([8.0]))
    assert read(SimpleNamespace(counters=[two])) == 25.0


def test_cell_added_as_new_files_is_found(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    cfg = json.load(open(os.path.join(BENCH, "configs/nasa_ipsc.json")))
    cfg["trace_jobs"] = 300
    (tmp_path / "bench/configs/nasa_short.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/grid_wide.json").write_text(json.dumps(
        {"entry": "replay_run", "schedulers": ["EASY PSUS"],
         "timeouts": [60, 120], "pool_seed": 5, "segments": 2,
         "check_calls": 1, "trace_seconds": 3}))
    (tmp_path / "bench/entries/replay_run.py").write_text(
        "class Entry:\n    def __init__(self, config, traffic):\n"
        "        self.scenarios = []\n")
    (tmp_path / "bench/metrics/new_counter.py").write_text(
        "def read(ctx):\n    return None\n")
    spec["configs"].append({"name": "nasa_short", "source": "x",
                            "file": "bench/configs/nasa_short.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "nasa_short.grid_wide",
                              "config": "nasa_short", "traffic": "grid_wide",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new_counter", "unit": "count",
                              "better": "lower", "source": "program_counter",
                              "layer": "device loop", "moves": "sim_jobs_per_s",
                              "workloads": ["nasa_short.grid_wide"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = load_cell("nasa_short.grid_wide", bench=str(tmp_path / "bench"))
    assert cell.config["trace_jobs"] == 300
    assert cell.traffic["timeouts"] == [60, 120]
    assert "new_counter" in cell.readers
    assert cell.entry({}, {}).scenarios == []
    assert "new_counter" not in load_cell(
        "nasa_ipsc.grid", bench=str(tmp_path / "bench")).readers


PARTITIONED = "islands.grid"


def _add_partitioned_cell(root):
    """A copy of the benchmark at ``root`` with a machine of three
    partitions (40 thin, 6 fat and 2 hybrid nodes, unequal speeds and
    power) under ``cheap`` order and grouped tables, and its cell on the
    ``grid`` mix: files and entries added, none changed."""
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    cfg = json.load(open(os.path.join(BENCH, "configs/nasa_ipsc.json")))
    groups = [group("thin", 40, 1.0, (9.0, 190.0, 120.0, 190.0, 9.0), 1800, 2700),
              group("fat", 6, 1.2, (15.0, 300.0, 200.0, 330.0, 15.0), 1800, 2700),
              group("hybrid", 2, 1.6, (20.0, 400.0, 260.0, 480.0, 20.0), 600, 900)]
    cfg.update(name="islands", platform={"nb_nodes": 48, "node_groups": groups})
    cfg["trace"].update(nb_res=48, max_res=32, mean_interarrival=150.0)
    cfg["engine"].update(node_order="cheap", grouped_tables=True,
                         allocation="partition")
    (root / "bench/configs/islands.json").write_text(json.dumps(cfg))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "islands", "source": "x",
                            "file": "bench/configs/islands.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": PARTITIONED, "config": "islands",
                              "traffic": "grid", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def _without_allocation(entry):
    """The entry as it was before it passed ``allocation`` on: the program
    then allocates across groups while the reference keeps partitions."""
    def make(cfg, mix):
        eng = {k: v for k, v in cfg["engine"].items() if k != "allocation"}
        return entry({**cfg, "engine": eng}, mix)

    return make


def test_partitioned_group_cell_added_as_files_is_cut_and_checked(tmp_path):
    _add_partitioned_cell(tmp_path / "src")
    cell = load_cell(PARTITIONED, bench=str(tmp_path / "src/bench"))
    assert cell.config["engine"]["allocation"] == "partition"
    assert set(cell.readers) == SWEEP  # sched_pass_share: listed cells only

    small = load_cell(PARTITIONED, bench=tiny.make(
        str(tmp_path / "cut"), root=str(tmp_path / "src")))
    plat = small.config["platform"]
    assert [g["count"] for g in plat["node_groups"]] == [13, 2, 2]
    assert plat["nb_nodes"] == small.config["trace"]["nb_res"] == 17
    assert small.config["trace"]["max_res"] == 13  # the thin partition

    def run(c, seed):
        return run_cell(c, seed, 0.2, False, time.perf_counter(),
                        require_chip=False, workdir=str(tmp_path / "w"),
                        log=lambda m: None)

    r = run(small, 2**31 + 41)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0
    r = run(dataclasses.replace(small, entry=_without_allocation(small.entry)),
            2**31 + 41)
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["schedule_mismatches"]["value"] > 0

    # the float32 control of the cell as added, through the check's verdict
    low = control.control(cell, 2**31 + 42, control._dtype("float32"))
    assert low["correct"] is False, low
    assert low["checks"]["schedule_mismatches"]["value"] == 0
