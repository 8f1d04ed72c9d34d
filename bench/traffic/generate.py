"""The benchmark's traffic generator: synthetic HPC job traces from a seed.

A copy of the simulator's seeded workload generator and SWF writer, kept
here so that no change to the program can change the yardstick. A
configuration file states the trace statistics of its deployment
(``trace``: machine size, mean interarrival, lognormal runtime mean and
coefficient of variation, request sizes, over-request factor); a traffic
mix file names the segment length. Every segment is a fresh trace of that
length drawn from the run's seed.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

_COLS = ("job_id", "res", "subtime", "reqtime", "runtime", "user_id")


def generate(stats: Dict, n_jobs: int, seed: int) -> Dict[str, np.ndarray]:
    """One trace of ``n_jobs`` jobs with the given statistics.

    Interarrivals are exponential, runtimes lognormal with the stated mean
    and coefficient of variation, sizes powers of two weighted towards
    small ones (``power_of_two``) or a squared-uniform draw between
    ``min_res`` and ``max_res``, and the requested time is the runtime
    times a uniform over-request factor in [1, ``overreq_factor``]."""
    rng = np.random.default_rng(seed)
    n = n_jobs
    nb_res = int(stats["nb_res"])
    max_res = int(stats.get("max_res") or nb_res)
    min_res = int(stats.get("min_res", 1))

    inter = rng.exponential(float(stats["mean_interarrival"]), size=n)
    subtime = np.floor(np.cumsum(inter)).astype(np.int64)
    subtime[0] = 0

    cv2 = float(stats["cv_runtime"]) ** 2
    sigma2 = np.log1p(cv2)
    mu = np.log(float(stats["mean_runtime"])) - sigma2 / 2.0
    runtime = np.maximum(
        1, np.round(rng.lognormal(mu, np.sqrt(sigma2), size=n))
    ).astype(np.int64)

    if stats.get("power_of_two", False):
        max_pow = int(np.log2(max_res))
        min_pow = int(np.ceil(np.log2(max(min_res, 1))))
        pows = np.arange(min_pow, max_pow + 1)
        w = 1.0 / (pows - min_pow + 1.0)
        res = 2 ** rng.choice(pows, size=n, p=w / w.sum())
    else:
        u = rng.uniform(size=n)
        res = np.clip(
            np.round(min_res + (max_res - min_res) * (u**2)), min_res, max_res
        ).astype(np.int64)

    over = rng.uniform(1.0, float(stats["overreq_factor"]), size=n)
    reqtime = np.maximum(1, np.round(runtime * over)).astype(np.int64)
    user = rng.integers(0, 16, size=n)
    return {
        "job_id": np.arange(n, dtype=np.int64),
        "res": np.asarray(res, np.int64),
        "subtime": subtime,
        "reqtime": reqtime,
        "runtime": runtime,
        "user_id": np.asarray(user, np.int64),
    }


def quick(n_jobs: int) -> Dict[str, np.ndarray]:
    """A warm-up trace of ``n_jobs`` one-node, one-second jobs, all
    submitted at once: the same shapes as a segment, over in a few batches."""
    one = np.ones(n_jobs, np.int64)
    return {
        "job_id": np.arange(n_jobs, dtype=np.int64),
        "res": one,
        "subtime": np.zeros(n_jobs, np.int64),
        "reqtime": one,
        "runtime": one,
        "user_id": np.zeros(n_jobs, np.int64),
    }


def write_swf(jobs: Dict[str, np.ndarray], path: str, max_procs: int) -> None:
    """Write a trace as a Standard Workload Format file (18 fields, -1 for
    what the simulator does not model, and a MaxProcs header)."""
    order = np.lexsort((jobs["job_id"], jobs["subtime"]))
    with open(path, "w") as f:
        f.write("; SWF written by the benchmark's traffic generator\n")
        f.write(f"; MaxProcs: {int(max_procs)}\n")
        for i in order:
            fields = [
                jobs["job_id"][i], jobs["subtime"][i], -1, jobs["runtime"][i],
                jobs["res"][i], -1, -1, jobs["res"][i], jobs["reqtime"][i],
                -1, 1, jobs["user_id"][i], -1, -1, -1, -1, -1, -1,
            ]
            f.write(" ".join(str(int(x)) for x in fields) + "\n")
