#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload nasa_ipsc.grid --seed 7 --seconds 25 --trace 0

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name from ``BENCHMARK.json``. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the end-to-end metrics, or with ``--trace 1`` the per-layer ones),
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``: each
number the correctness check compared, beside its limit. Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# libtpu's own log files would go to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from cells import load_cell
    from harness import NoChip, run_cell

    cell = load_cell(args.workload)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, log=log)
    except NoChip as e:
        log(f"[bench] {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"[check] {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
