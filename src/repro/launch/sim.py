"""Simulator driver — the paper's ``runner.py`` + ``simulator_config.yaml``
(§2.3.2/2.3.3), adapted to the offline container (JSON or minimal-YAML
config; PyYAML not required).

    PYTHONPATH=src python -m repro.launch.sim --config configs/sim_example.json
    PYTHONPATH=src python -m repro.launch.sim --workload wl.json --platform p.json \
        --scheduler "EASY PSUS" --timeout 900 --out out/run1
    PYTHONPATH=src python -m repro.launch.sim --experiment exp.json   # grid study

``--experiment`` runs a declarative :mod:`repro.experiments` spec: a whole
scheduler x timeout grid (x replications) as ONE compiled program.

Config keys (paper's runtime layer):
    workload:   path to workload.json | "preset:<name>" | "profiles"
    platform:   path to platform.json | node count (int); heterogeneous
                platforms use the "node_groups"/"nodes" JSON schema
                (core/SEMANTICS.md §Heterogeneity) and get per-group
                energy breakdowns in metrics.json
    scheduler:  "<FCFS|EASY> <PSUS|PSAS|PSAS+IPM|AlwaysOn|DVFS|Forecast|RL
                |RL:groups|RL:dvfs|<PSM>+DVFS|<PSM>+Forecast>"
                (the policy.from_label registry — single source of truth)
    timeout:    idle seconds before switch-off (null = never)
    forecast_horizon: rule 10 look-ahead seconds (only bites on
                '+Forecast' labels; null/0 = predict nothing)
    forecast_alpha:   rule 10 EWMA smoothing weight in [0, 1]
    terminate_overrun: bool
    node_order: "id" | "cheap" | "idle-watts" | "pack"
                (default: "cheap" when heterogeneous)
    allocation: "any" | "partition" — "partition" forbids cross-group
                allocations (core/SEMANTICS.md §Partition-aware
                allocation): a job takes the earliest-completing single
                node group that fits it, or fails to start
    rl:         {checkpoint: path, decision_interval: s}   (RL schedulers:
                checkpoint saved by training.checkpoint.save_policy; the
                greedy policy drives run_sim in-graph via an RLController)
    out:        output directory (CSV logs + metrics.json + gantt)
"""
from __future__ import annotations

import argparse
import csv
import dataclasses
import json
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.gantt import intervals_from_log, render_png, write_csv
from repro.core.metrics import metrics_from_state, np_state
from repro.core.policy import RLController, from_label, scheduler_labels
from repro.core.types import EngineConfig
from repro.experiments import (
    check_unknown_keys,
    resolve_platform,
    resolve_workload,
)
from repro.launch.compile_cache import use_compile_cache


# single-run config keys (the experiment layer validates its own spec)
_KNOWN_KEYS = {
    "workload", "platform", "scheduler", "timeout", "terminate_overrun",
    "node_order", "allocation", "rl", "gantt", "out", "grouped_tables",
    "merge_bursts", "forecast_horizon", "forecast_alpha",
}
_KNOWN_RL_KEYS = {"checkpoint", "decision_interval"}


def _validate_keys(config: Dict[str, Any]) -> None:
    """Reject unknown config keys loudly instead of silently ignoring typos."""
    check_unknown_keys(config, _KNOWN_KEYS, "config")
    rl = config.get("rl")
    if isinstance(rl, dict):
        check_unknown_keys(rl, _KNOWN_RL_KEYS, "rl config")


def _load_mini_yaml(path: str) -> Dict[str, Any]:
    """JSON, or a flat ``key: value`` YAML subset (no PyYAML offline)."""
    with open(path) as f:
        text = f.read()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        pass
    out: Dict[str, Any] = {}
    for line in text.splitlines():
        line = line.split("#", 1)[0].strip()
        if not line or ":" not in line:
            continue
        k, v = line.split(":", 1)
        v = v.strip()
        if v.lower() in ("null", "none", ""):
            out[k.strip()] = None
        elif v.lower() in ("true", "false"):
            out[k.strip()] = v.lower() == "true"
        else:
            try:
                out[k.strip()] = int(v)
            except ValueError:
                try:
                    out[k.strip()] = float(v)
                except ValueError:
                    out[k.strip()] = v.strip("'\"")
    return out


def _checkpoint_controller(params, meta):
    """Greedy in-graph controller: features -> argmax logits -> commands."""
    from repro.core.rl.actions import ACTION_TRANSLATORS
    from repro.core.rl.features import FEATURE_EXTRACTORS
    from repro.core.rl.networks import policy_apply

    extract = FEATURE_EXTRACTORS[meta["feature"]]
    translate = ACTION_TRANSLATORS[meta["action"]]
    window = meta.get("feature_window", 8)

    def controller(s, const):
        if meta["feature"] == "queue_window":
            obs = extract(s, const, window)
        else:
            obs = extract(s, const)
        logits, _ = policy_apply(params, obs)
        return translate(s, const, jnp.argmax(logits), meta["n_levels"])

    return controller


def _resolve_rl_policy(pol, config, plat):
    """Attach the checkpointed greedy controller to an RLController policy."""
    from repro.core.rl.features import feature_size
    from repro.training.checkpoint import load_policy

    rl = config.get("rl") or {}
    if "checkpoint" not in rl:
        raise ValueError(
            "RL schedulers need an rl: {checkpoint: <dir>} config block "
            "(a policy saved by training.checkpoint.save_policy)"
        )
    params, meta = load_policy(rl["checkpoint"])
    expected_obs = feature_size(
        meta["feature"], meta.get("feature_window", 8), plat.n_groups()
    )
    if meta["obs_size"] != expected_obs:
        raise ValueError(
            f"RL checkpoint obs_size={meta['obs_size']} does not fit this "
            f"platform ({plat.n_groups()} node groups -> obs_size "
            f"{expected_obs} for feature {meta['feature']!r}); retrain or "
            "pick a matching platform"
        )
    if bool(meta.get("grouped", False)) != pol.grouped:
        raise ValueError(
            f"RL checkpoint was trained with grouped={meta.get('grouped')} "
            f"actions but scheduler label requests grouped={pol.grouped}; "
            "use the matching 'RL' / 'RL:groups' label"
        )
    if bool(meta.get("dvfs", False)) != pol.dvfs:
        raise ValueError(
            f"RL checkpoint was trained with dvfs={meta.get('dvfs', False)} "
            f"but scheduler label requests dvfs={pol.dvfs}; use the "
            "matching 'RL' / 'RL:dvfs' label"
        )
    if pol.dvfs:
        from repro.core.rl.actions import DVFS_ACTIONS

        if meta["action"] in DVFS_ACTIONS and meta["n_levels"] != plat.n_dvfs_modes():
            raise ValueError(
                f"RL checkpoint commands {meta['n_levels']} DVFS modes but "
                f"this platform's mode-table width is {plat.n_dvfs_modes()}"
                "; mode commands would be mis-decoded — retrain or pick a "
                "matching platform"
            )
    if pol.grouped:
        from repro.core.rl.actions import action_space_size

        ckpt_groups = int(meta.get("n_groups", 1))
        expected_actions = action_space_size(
            meta["action"], meta["n_levels"], plat.n_groups()
        )
        if ckpt_groups != plat.n_groups() or meta["n_actions"] != expected_actions:
            raise ValueError(
                f"RL checkpoint was trained for {ckpt_groups} node groups "
                f"({meta['n_actions']} actions) but this platform has "
                f"{plat.n_groups()} groups ({expected_actions} actions for "
                f"action {meta['action']!r}); group-targeted commands would "
                "be mis-decoded — retrain or pick a matching platform"
            )
    controller = _checkpoint_controller(params, meta)
    return dataclasses.replace(pol, controller=controller), rl


def _device_bytes_limit() -> Optional[int]:
    """Bytes the default device can hold, where its backend reports it."""
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("bytes_limit")


def _check_gantt_fits(cap: int, n_nodes: int) -> None:
    """Refuse, before compiling, a Gantt log the device cannot hold: the
    log is two i32[cap, N] per-batch snapshots (``engine.run_sim_gantt``)."""
    need = 2 * cap * n_nodes * 4
    limit = _device_bytes_limit()
    if limit is not None and need > limit:
        raise ValueError(
            f"the Gantt log needs {need} bytes of device memory (2 x "
            f"{cap} batches x {n_nodes} nodes x i32), more than the "
            f"device's {limit}; set \"gantt\": false in the config to run "
            "without it"
        )


def run(config: Dict[str, Any]) -> Dict[str, Any]:
    _validate_keys(config)
    wl = resolve_workload(config["workload"])
    plat = resolve_platform(config.get("platform", wl.nb_res))
    sched = config.get("scheduler", "EASY PSUS")
    base, pol = from_label(sched)
    rl_interval = None
    if isinstance(pol, RLController):
        pol, rl = _resolve_rl_policy(pol, config, plat)
        rl_interval = rl.get("decision_interval")
    # heterogeneous platforms default to cost-aware node selection
    # (core/SEMANTICS.md §Heterogeneity); override with node_order: id
    node_order = config.get(
        "node_order", "cheap" if plat.is_heterogeneous else "id"
    )
    ecfg = EngineConfig(
        base=base,
        policy=pol,
        timeout=config.get("timeout"),
        terminate_overrun=bool(config.get("terminate_overrun", False)),
        record_gantt=bool(config.get("gantt", True)),
        node_order=node_order,
        # §Partition-aware allocation: forbid cross-group allocations
        allocation=config.get("allocation", "any"),
        rl_decision_interval=rl_interval,
        grouped_tables=bool(config.get("grouped_tables", False)),
        merge_bursts=bool(config.get("merge_bursts", False)),
        # rule 10 operands (§Forecast) — only bite on '+Forecast' labels
        forecast_horizon=config.get("forecast_horizon"),
        forecast_alpha=float(config.get("forecast_alpha", 0.25)),
    )
    out_dir = config.get("out", "out/sim")
    os.makedirs(out_dir, exist_ok=True)

    s0 = engine.init_state(plat, wl, ecfg)
    # single-config run: fold the policy flags in as closure constants so
    # the program traces only this scheduler's rules (§Static specialization)
    const = engine.make_const(plat, ecfg, specialize=True)
    cap = engine.default_batch_cap(len(wl))
    if ecfg.record_gantt:
        _check_gantt_fits(cap, plat.nb_nodes)
        s, log = engine.run_sim_gantt(s0, const, ecfg, max_batches=cap)
        intervals = intervals_from_log(log)
        write_csv(intervals, os.path.join(out_dir, "gantt.csv"))
        d = np_state(s)
        render_png(
            intervals,
            os.path.join(out_dir, "gantt.png"),
            terminated_jobs=[int(j) for j in d["job_terminated"].nonzero()[0]],
            title=f"{sched} timeout={ecfg.timeout}",
        )
    else:
        s = engine.simulate(plat, wl, ecfg)

    m = metrics_from_state(s, plat)
    if m.truncated and ecfg.record_gantt:
        # engine.simulate already warns for the non-gantt path; keep the
        # gantt path just as loud — a capped run must not read as finished
        import warnings

        warnings.warn(
            f"run {sched!r} hit the batch cap ({cap}) before completing — "
            "metrics.json describes a PARTIAL simulation ('truncated': "
            "true). Raise max_batches to run to completion.",
            RuntimeWarning,
            stacklevel=2,
        )

    # CSV job log (paper §2.3.3: "CSV outputs including job execution logs")
    d = np_state(s)
    with open(os.path.join(out_dir, "jobs.csv"), "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["job", "res", "subtime", "start", "finish", "wait", "terminated"])
        arrs = wl.arrays()
        for i in range(len(wl)):
            if not d["job_exists"][i]:
                continue
            w.writerow(
                [
                    int(arrs["job_id"][i]), int(d["job_res"][i]),
                    int(d["job_subtime"][i]), int(d["job_start"][i]),
                    int(d["job_finish"][i]),
                    int(d["job_start"][i] - d["job_subtime"][i]),
                    bool(d["job_terminated"][i]),
                ]
            )
    result = {"scheduler": sched, "timeout": ecfg.timeout, **m.row()}
    with open(os.path.join(out_dir, "metrics.json"), "w") as f:
        json.dump(result, f, indent=2)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None)
    ap.add_argument(
        "--experiment", default=None, metavar="SPEC.json",
        help="run a declarative repro.experiments grid spec "
             "(scheduler x timeout grid as ONE compiled program)",
    )
    ap.add_argument("--workload", default=None)
    ap.add_argument("--platform", default=None)
    ap.add_argument(
        "--scheduler",
        default="EASY PSUS",
        metavar="LABEL",
        help="a policy.from_label scheduler label: "
             f"{', '.join(scheduler_labels(include_rl=True, include_dvfs=True))}"
             ", or '<PSM>+DVFS' / '<PSM>+Forecast' composing rule 9 / "
             "rule 10 onto any stack (e.g. 'EASY PSAS+IPM+DVFS', "
             "'EASY PSUS+Forecast')",
    )
    ap.add_argument("--timeout", type=int, default=None)
    ap.add_argument("--terminate-overrun", action="store_true")
    ap.add_argument("--out", default="out/sim")
    args = ap.parse_args(argv)
    use_compile_cache()
    try:
        from_label(args.scheduler)
    except KeyError as e:
        # registry validation with the did-you-mean hint, instead of a
        # frozen argparse choices list drifting from from_label
        ap.error(str(e.args[0]) if e.args else str(e))

    if args.experiment:
        # the spec is the whole study: reject single-run flags rather than
        # silently ignoring them (the same loud-failure contract as
        # _validate_keys)
        clashing = [
            f"--{name.replace('_', '-')}"
            for name in (
                "config", "workload", "platform", "scheduler", "timeout",
                "terminate_overrun", "out",
            )
            if getattr(args, name) != ap.get_default(name)
        ]
        if clashing:
            ap.error(
                f"--experiment runs a self-contained spec; {', '.join(clashing)} "
                "would be ignored — set the equivalent field in the spec file"
            )
        from repro.experiments import run_file

        result = run_file(args.experiment)
        print(result.table())
        print(
            f"# grid: {len(result.rows)} rows, "
            f"{result.n_compiles if result.n_compiles is not None else '?'} "
            f"compiled program(s), {result.wall_s:.2f}s "
            f"({result.jobs_per_s:.0f} simulated jobs/s)"
        )
        return result

    if args.config:
        config = _load_mini_yaml(args.config)
    else:
        config = {
            "workload": args.workload or "preset:fig3_small",
            "scheduler": args.scheduler,
            "timeout": args.timeout,
            "terminate_overrun": args.terminate_overrun,
            "out": args.out,
        }
        if args.platform:
            config["platform"] = (
                int(args.platform) if args.platform.isdigit() else args.platform
            )
    result = run(config)
    print(json.dumps(result, indent=2))
    return result


if __name__ == "__main__":
    main()
