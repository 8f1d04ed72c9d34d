"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration's file is
the one ``BENCHMARK.json`` gives it, the mix is ``traffic/<name>.json``,
the entry point the mix drives is ``entries/<mix's entry>.py`` and each
per-layer metric is ``metrics/<name>.py``. Nothing here knows a cell, a
configuration, a mix, an entry or a metric by name, so a later change adds
one by adding files and entries alone.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration's file
    traffic: dict  # the mix's file
    end_to_end: List[dict]  # the entries that this cell reports
    per_layer: List[dict]
    readers: Dict[str, Callable]  # per-layer metric name -> read(ctx)
    entry: Callable  # Entry(config, traffic) of the mix's entry point


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _module(bench: str, kind: str, name: str):
    path = os.path.join(bench, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench: str, name: str) -> Callable:
    """``read`` of ``metrics/<name>.py``."""
    return _module(bench, "metrics", name).read


def load_cell(name: str, bench: str = BENCH) -> Cell:
    """The cell ``name`` of ``<bench>/../BENCHMARK.json`` with its files."""
    root = os.path.dirname(bench)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    per_layer = [m for m in spec["per_layer"] if _reports(m, name)]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=per_layer,
        readers={m["name"]: load_reader(bench, m["name"]) for m in per_layer},
        entry=_module(bench, "entries", traffic["entry"]).Entry,
    )
