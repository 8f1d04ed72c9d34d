"""Every cell resolves its files by name, and a cell added as files alone
is found."""
import json
import os
import shutil

import pytest

from cells import BENCH, load_cell

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
