"""The comparison that decides ``correct``.

After the window has closed, a sample of its calls, drawn from the seed, is
replayed by the plain reference (``reference/des.py``) on the same
segment, and every sampled scenario's answer is compared with it:

* ``schedule_mismatches``: jobs whose start, finish or terminated flag
  differs from the reference's (every job, where the counts of jobs
  differ), and scenarios whose makespan or count of terminated jobs
  differs. Exact: limit 0.
* ``energy_rel_err``: the largest gap of a reported energy (total, wasted,
  and each node group's) from the reference's, over the reference's total.

In a grid, the sampled scenarios hold each scheduler of the policy axis
once, at a timeout drawn from the seed, and at least one scenario of every
chip the grid was sharded over. The limits are the configuration's
(``limits``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from reference import des


@dataclasses.dataclass
class Output:
    """What the program answered for one simulated scenario."""

    schedule: np.ndarray  # (J, 3) [start, finish, terminated]
    energy_j: Dict[str, float]  # total, wasted and per-group joules
    makespan_s: float
    n_terminated: int


@dataclasses.dataclass
class Numbers:
    schedule_mismatches: int = 0
    energy_rel_err: float = 0.0
    compared: int = 0  # scenarios compared

    def add(self, mism: int, rel: float) -> None:
        self.schedule_mismatches += int(mism)
        self.energy_rel_err = max(self.energy_rel_err, float(rel))
        self.compared += 1


def sample_calls(n_calls: int, k: int, rng: np.random.Generator) -> List[int]:
    """Up to ``k`` of the window's calls, the last one always among them."""
    if n_calls <= k:
        return list(range(n_calls))
    rest = rng.choice(n_calls - 1, size=k - 1, replace=False)
    return sorted(int(i) for i in rest) + [n_calls - 1]


def sample_lanes(scenarios: Sequence[Tuple[str, int]], devices: int,
                 rng: np.random.Generator) -> List[int]:
    """One lane per scheduler (at a drawn timeout), then one more lane on
    each chip that holds none yet (lanes are split in equal contiguous
    blocks over the chips, padded to a multiple of their count)."""
    K = len(scenarios)
    labels = dict.fromkeys(s for s, _ in scenarios)
    lanes = [int(rng.choice([i for i, (s, _) in enumerate(scenarios) if s == lab]))
             for lab in labels]
    per = (K + (-K) % devices) // devices
    for d in range(devices):
        own = range(d * per, min(K, (d + 1) * per))
        if len(own) and not any(i in own for i in lanes):
            lanes.append(int(rng.choice(own)))
    return sorted(lanes)


def reference(config: dict, jobs: dict, label: str, timeout: Optional[int],
              energy_dtype=np.float64) -> des.Result:
    """The reference's run of one scenario."""
    plat = des.Platform.from_json(config["platform"])
    eng = config["engine"]
    return des.simulate(plat, des.prepare_jobs(jobs, plat.n), label, timeout,
                        node_order=eng["node_order"], window=int(eng["window"]),
                        allocation=eng.get("allocation", "any"),
                        energy_dtype=energy_dtype)


def energy_of(res: des.Result) -> Dict[str, float]:
    out = {"total_energy_kwh": res.total_energy_j,
           "wasted_energy_kwh": res.wasted_energy_j}
    if len(res.group_names) > 1:
        for name, row in zip(res.group_names, res.energy):
            out[f"energy_kwh.{name}"] = float(row.sum())
    return out


def compare(got: Output, ref: des.Result) -> Tuple[int, float]:
    """(mismatches, energy relative error) of one scenario's answer."""
    want = ref.schedule()
    if got.schedule.shape != want.shape:
        mism = max(len(got.schedule), len(want))
    else:
        mism = int(np.count_nonzero(np.any(got.schedule != want, axis=1)))
    done = ref.finish >= 0
    makespan = float(ref.finish[done].max()) if done.any() else 0.0
    mism += int(got.makespan_s != makespan)
    mism += int(got.n_terminated != int(ref.terminated[done].sum()))
    want_e = energy_of(ref)
    total = max(want_e["total_energy_kwh"], 1e-30)
    rel = 0.0
    for k, w in want_e.items():
        g = got.energy_j.get(k)
        rel = max(rel, float("inf") if g is None or not np.isfinite(g)
                  else abs(g - w) / total)
    return mism, rel


def verdict(numbers: Numbers, limits: dict) -> Tuple[bool, dict]:
    """``correct`` and each number beside its limit."""
    shown = {
        "schedule_mismatches": {"value": numbers.schedule_mismatches,
                                "limit": limits["schedule_mismatches"]},
        "energy_rel_err": {"value": numbers.energy_rel_err,
                           "limit": limits["energy_rel_err"]},
        "scenarios_compared": {"value": numbers.compared, "limit": 1},
    }
    ok = (numbers.schedule_mismatches <= limits["schedule_mismatches"]
          and numbers.energy_rel_err <= limits["energy_rel_err"]
          and numbers.compared >= 1)
    return ok, shown
