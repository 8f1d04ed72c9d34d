#!/usr/bin/env python3
"""The control of the correctness check: the reference put in the program's
place with its energy ledger one precision lower, judged by the same check.

    python3 bench/control.py --workload nasa_ipsc.grid --seeds 1 2 3 [--dtype float32]

The configurations state a float32 energy ledger with compensated
summation; the control keeps the ledger in plain float32, the next
precision down and the step that would tempt a later change (``--dtype
bfloat16`` goes one further). For each seed it takes the calls and lanes
that a run's check would sample from the cell's pool (one round of the
window), runs the float64 reference and the lower one on each, and prints
the check's numbers and verdict, one JSON line a seed. A sound check says
``correct: false``. It runs on the host; the benchmark's runs never call it.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

import check  # noqa: E402
from cells import load_cell  # noqa: E402
from harness import CHECK, pool, rounds, segment_seed  # noqa: E402


def _dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes

        return ml_dtypes.bfloat16
    return np.dtype(name)


def scenarios_of(mix: dict):
    return [(s, t) for s in mix["schedulers"] for t in mix["timeouts"]]


def control(cell, seed: int, dtype) -> dict:
    """The check's numbers with the lower-precision reference as the program."""
    cfg, mix = cell.config, cell.traffic
    rng = np.random.default_rng(segment_seed(seed, CHECK))
    scen = scenarios_of(mix)
    segments = pool(cfg, mix)
    order = next(rounds(seed, len(segments)))
    nums = check.Numbers()
    for i in check.sample_calls(len(order), int(mix["check_calls"]), rng):
        jobs = segments[order[i]]
        for lane in check.sample_lanes(scen, int(mix.get("devices", 1)), rng):
            label, timeout = scen[lane]
            ref = check.reference(cfg, jobs, label, timeout)
            low = check.reference(cfg, jobs, label, timeout, energy_dtype=dtype)
            done = low.finish >= 0
            got = check.Output(low.schedule(), check.energy_of(low),
                               float(low.finish[done].max()) if done.any() else 0.0,
                               int(low.terminated[done].sum()))
            nums.add(*check.compare(got, ref))
    ok, shown = check.verdict(nums, cfg["limits"])
    return {"seed": seed, "correct": ok, "checks": shown}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    for seed in args.seeds:
        print(json.dumps(control(cell, seed, _dtype(args.dtype))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
