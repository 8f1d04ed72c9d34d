"""A copy of the benchmark's files with the cells cut to a size the CPU
runs in seconds: the same entries, mixes, platforms and checks, on a few
dozen nodes and jobs. Tests run the harness on it with the look for a chip
skipped."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


def make(tmp: str) -> str:
    """``<tmp>/bench`` with ``<tmp>/BENCHMARK.json``: every cell of the real
    benchmark, each configuration cut to 16 nodes and 40 jobs a segment,
    each pool to 2 segments and each grid to 2 schedulers x 2 timeouts per
    chip."""
    bench = os.path.join(tmp, "bench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    spec = _load(os.path.join(ROOT, "BENCHMARK.json"))
    for c in spec["configs"]:
        cfg = _load(os.path.join(ROOT, c["file"]))
        cfg["platform"]["nb_nodes"] = 16
        cfg["trace"].update(nb_res=16, mean_interarrival=200.0)
        cfg["trace_jobs"] = 40
        _dump(cfg, os.path.join(tmp, c["file"]))
    for w in spec["workloads"]:
        path = os.path.join(bench, "traffic", f"{w['traffic']}.json")
        mix = _load(path)
        mix.update(segments=2, check_calls=2, trace_seconds=3,
                   schedulers=["FCFS PSUS", "EASY PSAS+IPM"],
                   timeouts=[300, 1800] * int(mix.get("devices", 1)))
        _dump(mix, path)
    _dump(spec, os.path.join(tmp, "BENCHMARK.json"))
    return bench
