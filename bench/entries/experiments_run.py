"""Entry ``experiments_run``: one scheduler x timeout grid per call through
``experiments.run`` (SWF parse, resolve, ``engine.sweep``, rows,
``metrics.json`` and ``rows.csv``), sharded over ``devices`` chips when the
mix asks for more than one.

The mix names the schedulers, the timeouts and the chips; the configuration
gives the platform (the repository's platform JSON schema) and the engine
options, each of which is passed to the program.
"""
from __future__ import annotations

import os
from typing import Dict, List

import numpy as np

from check import Output

DONE = 3  # job status of a finished job in the engine's state
FIELDS = ("n_batches", "job_status", "job_start", "job_finish", "job_terminated")


def _energy_fields(row: dict) -> Dict[str, float]:
    keys = ["total_energy_kwh", "wasted_energy_kwh"] + sorted(
        k for k in row if k.startswith("energy_kwh."))
    return {k: float(row[k]) * 3.6e6 for k in keys}


class Entry:
    engine_call = "sweep"  # the engine function each call goes through

    @staticmethod
    def keep(call, batch) -> None:
        """Keep the sweep's final states on the device, and its lanes and chips."""
        call.engine_out = {k: getattr(batch.states, k) for k in FIELDS}
        call.lanes = len(batch.metrics)
        call.devices = batch.devices or 1

    def __init__(self, config: dict, traffic: dict):
        from repro import experiments

        self._exp = experiments
        self.n_nodes = int(config["platform"]["nb_nodes"])
        self.config = config
        self.schedulers = tuple(traffic["schedulers"])
        self.timeouts = tuple(traffic["timeouts"])
        self.scenarios = [(s, t) for s in self.schedulers for t in self.timeouts]
        self.devices = int(traffic.get("devices", 1))

    def call(self, swf: str, out_dir: str):
        eng = self.config["engine"]
        exp = self._exp.Experiment(
            name=os.path.basename(out_dir),
            workload={"swf": swf, "nb_nodes": self.n_nodes, "oversize": "clamp"},
            platform=self.config["platform"],
            schedulers=self.schedulers,
            timeouts=self.timeouts,
            node_order=eng["node_order"],
            allocation=eng.get("allocation", "any"),
            grouped_tables=bool(eng["grouped_tables"]),
            window=int(eng["window"]),
            out=out_dir,
        )
        return self._exp.run(exp, devices=self.devices if self.devices > 1 else None)

    def outputs(self, result, out_dir: str, engine_out,
                lanes: List[int]) -> Dict[int, Output]:
        """Each lane's row as ``experiments.run`` returned it, with its
        per-job schedule from the final states of the sweep it ran."""
        status = np.asarray(engine_out["job_status"])
        start = np.asarray(engine_out["job_start"])
        finish = np.asarray(engine_out["job_finish"])
        term = np.asarray(engine_out["job_terminated"])
        out = {}
        for lane in lanes:
            row = result.rows[lane]
            done = status[lane] == DONE
            sched = np.stack([start[lane], np.where(done, finish[lane], -1),
                              term[lane].astype(np.int64)], axis=1)
            out[lane] = Output(sched.astype(np.int64), _energy_fields(row),
                               float(row["makespan_s"]), int(row["n_terminated"]))
        return out
