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


NODES = 16  # the cut machine's size, about


def cut(cfg: dict) -> dict:
    """``cfg`` on about :data:`NODES` nodes and 40 jobs a segment.

    A homogeneous machine gets 16 nodes. A machine of node groups keeps
    every group: each group's count is scaled to its share of 16, rounded,
    and never below 2, and the machine is their sum. Its trace's widest job
    then fits the largest group where the configuration allocates within
    one group (``"allocation": "partition"``), and the machine otherwise,
    so that every job can start."""
    plat, trace = cfg["platform"], cfg["trace"]
    groups = plat.get("node_groups")
    if groups:
        total = sum(int(g["count"]) for g in groups)
        for g in groups:
            g["count"] = max(2, round(NODES * int(g["count"]) / total))
        n = sum(g["count"] for g in groups)
        widest = (max(g["count"] for g in groups)
                  if cfg["engine"].get("allocation") == "partition" else n)
        trace["max_res"] = min(int(trace.get("max_res") or n), widest)
    else:
        n = NODES
    plat["nb_nodes"] = n
    trace.update(nb_res=n, mean_interarrival=200.0)
    cfg["trace_jobs"] = 40
    return cfg


def make(tmp: str, root: str = ROOT) -> str:
    """``<tmp>/bench`` with ``<tmp>/BENCHMARK.json``: every cell of the
    benchmark at ``root``, each configuration cut by :func:`cut`, each pool
    to 2 segments and each grid to 2 schedulers x 2 timeouts per chip."""
    bench = os.path.join(tmp, "bench")
    shutil.copytree(os.path.join(root, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = _load(os.path.join(root, "BENCHMARK.json"))
    for c in spec["configs"]:
        cfg = cut(_load(os.path.join(root, c["file"])))
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
