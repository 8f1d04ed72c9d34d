#!/usr/bin/env python3
"""Run the simulator's main paths once on one TPU chip, through the entry
points users call, at deployment width, and check what comes out.

    python3 chip_smoke.py                # one chip: the six phases below
    python3 chip_smoke.py --four-chips   # four chips: the sharded paths only

Phases (one process; each phase is a function that ``main`` calls):

1. device       -- JAX must find a TPU; nothing runs on the CPU instead.
2. paper run    -- ``launch/sim.main`` on the NASA iPSC preset (2,000 jobs,
                   128 nodes, EASY PSAS+IPM, Gantt log on); the schedule is
                   bit-exact with the PyDES oracle, energy within rel 1e-5.
3. Curie replay -- the synthesized 10,000-job Curie trace at 11,200 nodes
                   through ``launch/sim.run``, once per event-pass route
                   (grouped tables -> ``event_fuse_occ``; a homogeneous
                   platform -> ``event_fuse_ledger``). Every job finishes,
                   the compiled program holds the Pallas kernel, and a
                   300-job prefix at full width agrees with the oracle.
4. grid         -- the paper's Figs. 4/5 grid (6 schedulers x 4 timeouts)
                   through ``launch/sim.main --experiment``: one compiled
                   program, sampled rows agree with the oracle.
5. service      -- ``launch/sim_serve.SimService`` answers two same-shaped
                   requests; the second is all compile-cache hits.
6. RL           -- a few ``train_a2c`` updates (16 envs, 128 nodes), the
                   policy saved with ``save_policy`` and replayed as
                   ``EASY RL`` through ``launch/sim.main``.

``--four-chips`` runs only the device-sharded paths and what they are
compared with: the step-4 grid with ``devices=4`` against one device (rows
byte-identical, one compiled program), and ``train_a2c(devices=4)``.

Outputs land under ``out/chip_smoke/``. Each phase prints its set-up
seconds (tracing and compiling) and wall seconds; the last line of standard
output is one JSON object naming the device. Any failure exits non-zero
before that line is printed.
"""
from __future__ import annotations

import argparse
import contextlib
import csv
import json
import math
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import experiments  # noqa: E402
from repro.core import engine  # noqa: E402
from repro.core.policy import from_label, scheduler_labels  # noqa: E402
from repro.core.ref.pydes import run_pydes  # noqa: E402
from repro.core.types import DONE, EngineConfig  # noqa: E402
from repro.launch import sim  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.sim_serve import SimService  # noqa: E402
from repro.workloads.platform import PlatformSpec, curie_platform  # noqa: E402
from repro.workloads.traces import synthesize_curie_swf  # noqa: E402

OUT = os.path.join(ROOT, "out", "chip_smoke")
KERNEL_MARK = "tpu_custom_call"  # a Pallas kernel in compiled TPU text
ENERGY_RTOL = 1e-5  # engine (Kahan f32) vs oracle (f64), SEMANTICS §Numerics
# the six timeout-based schedulers of the paper's Figs. 4/5
GRID_SCHEDULERS = tuple(l for l in scheduler_labels() if "AlwaysOn" not in l)
GRID_TIMEOUTS = (300, 900, 1800, 3600)


# --------------------------------------------------------------- accounting

_COUNTS = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}


def _on_duration(event: str, duration: float, **_) -> None:
    if event.startswith("/jax/core/compile/"):
        _COUNTS["compile_s"] += duration


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _COUNTS["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _COUNTS["cache_misses"] += 1


@contextlib.contextmanager
def timed(label: str, info: dict = None):
    """Print the wall seconds of the block and the share spent tracing and
    compiling (set-up), with the persistent-cache hits and misses in it."""
    before = dict(_COUNTS)
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    setup = _COUNTS["compile_s"] - before["compile_s"]
    fields = {
        "setup_s": setup,
        "wall_s": wall,
        "cache_hits": _COUNTS["cache_hits"] - before["cache_hits"],
        "cache_misses": _COUNTS["cache_misses"] - before["cache_misses"],
        **(info or {}),
    }
    print(f"[{label}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


@contextlib.contextmanager
def captured_simulate():
    """Record each ``engine.simulate`` call that ``launch/sim.run`` makes:
    its platform, workload, config and final state. The metrics row that
    ``run`` returns leaves out the batch count and per-job status."""
    calls = []
    real = engine.simulate

    def spy(platform, workload, config, **kw):
        state = real(platform, workload, config, **kw)
        calls.append((platform, workload, config, state))
        return state

    engine.simulate = spy
    try:
        yield calls
    finally:
        engine.simulate = real


# ------------------------------------------------------------------- checks

def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def read_schedule(run_dir: str) -> np.ndarray:
    """``jobs.csv`` as the oracle's (n_jobs, 3) [start, finish, terminated]."""
    with open(os.path.join(run_dir, "jobs.csv"), newline="") as f:
        rows = list(csv.DictReader(f))
    return np.array(
        [
            [float(r["start"]), float(r["finish"]),
             float(r["terminated"] == "True")]
            for r in rows
        ]
    ).reshape(len(rows), 3)


def run_oracle(platform, workload, config):
    """PyDES on a run's inputs: (metrics, simulator, seconds)."""
    t0 = time.perf_counter()
    m_ref, des = run_pydes(platform, workload, config)
    return m_ref, des, time.perf_counter() - t0


def check_against_oracle(run_dir, result, oracle, what) -> float:
    """The run's schedule is bit-exact with the oracle's and its energy
    agrees to ``ENERGY_RTOL``; returns the relative energy deviation."""
    m_ref, des, _ = oracle
    ref = des.schedule_table()
    got = read_schedule(run_dir)
    check(got.shape == ref.shape, f"{what}: {got.shape} jobs vs oracle {ref.shape}")
    bad = np.flatnonzero(np.any(got != ref, axis=1))
    check(
        bad.size == 0,
        f"{what}: schedule differs from the oracle at {bad.size} job(s), "
        f"first job {bad[:1].tolist()}: {got[bad[:1]].tolist()} vs "
        f"{ref[bad[:1]].tolist()}",
    )
    e_got = result["total_energy_kwh"] * 3.6e6
    rel = abs(e_got - m_ref.total_energy_j) / m_ref.total_energy_j
    check(
        rel <= ENERGY_RTOL,
        f"{what}: energy {e_got} J vs oracle {m_ref.total_energy_j} J "
        f"(rel {rel:.3e} > {ENERGY_RTOL})",
    )
    return rel


def program_text(platform, workload, config) -> str:
    """Compiled text of the program ``engine.simulate`` cached for these
    inputs (the one the run executed), rebuilt from its cache entry."""
    config = engine.trim_window(config, len(workload))
    s0 = engine.init_state(platform, workload, config)
    const = engine.make_const(platform, config, specialize=True)
    cap = config.max_batches or engine.default_batch_cap(len(workload))
    key = engine._static_trace_key(
        platform, config, int(s0.job_status.shape[0]), cap
    ) + (const.policy,)
    fn = engine._SIM_FNS[key]
    return fn.lower(s0, const._replace(policy=None)).compile().as_text()


# ------------------------------------------------------------------- phases

def phase_device(platform: str = "tpu", min_count: int = 1) -> dict:
    """The device JAX found; fails unless it is ``platform``."""
    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    check(
        info["platform"] == platform,
        f"JAX found {info['platform']} devices, not {platform}; refusing to "
        "run the smoke anywhere else",
    )
    check(info["count"] >= min_count,
          f"{info['count']} device(s) found, {min_count} needed")
    return info


def phase_paper_run(out_dir: str, workload: str = "preset:nasa_ipsc",
                    nodes: int = 128) -> dict:
    """The paper's single run through the default CLI path, Gantt log on."""
    label, timeout = "EASY PSAS+IPM", 900
    run_dir = os.path.join(out_dir, "paper_run")
    with timed("paper_run"):
        result = sim.main([
            "--workload", workload, "--platform", str(nodes),
            "--scheduler", label, "--timeout", str(timeout), "--out", run_dir,
        ])
    for name in ("metrics.json", "jobs.csv", "gantt.csv", "gantt.png"):
        check(os.path.exists(os.path.join(run_dir, name)),
              f"paper run wrote no {name}")
    check(not result.get("truncated", False), "paper run was truncated")
    base, pol = from_label(label)
    oracle = run_oracle(
        experiments.resolve_platform(nodes),
        experiments.resolve_workload(workload),
        EngineConfig(base=base, policy=pol, timeout=timeout),
    )
    rel = check_against_oracle(run_dir, result, oracle, "paper run")
    print(f"[paper_run] oracle: schedule bit-exact, energy rel {rel:.3e} "
          f"(oracle {oracle[2]}s)", flush=True)
    return result


def replay_curie(swf: str, route: str, platform, nodes: int, jobs: int,
                 grouped: bool, out_dir: str):
    """The first ``jobs`` jobs of the trace through ``launch/sim.run``, every
    one of which must finish; returns (run dir, result, (platform,
    workload, config), final state)."""
    run_dir = os.path.join(out_dir, f"curie_{route}_{jobs}")
    config = {
        "workload": {"swf": swf, "nb_nodes": nodes, "oversize": "clamp",
                     "max_jobs": jobs},
        "platform": platform,
        "scheduler": "EASY PSUS",
        "timeout": 1800,
        "gantt": False,
        "grouped_tables": grouped,
        "out": run_dir,
    }
    with captured_simulate() as calls, timed("curie", {"route": route,
                                                       "jobs": jobs}):
        result = sim.run(config)
    (plat, wl, cfg, state), = calls
    status = np.asarray(state.job_status)
    print(f"[curie] route={route} jobs={jobs} "
          f"n_batches={int(state.n_batches)}", flush=True)
    check(len(wl) == jobs, f"{route}: replayed {len(wl)} jobs")
    check(not bool(state.truncated), f"{route}: run was truncated")
    check(bool(np.all(status == DONE)),
          f"{route}: {int(np.sum(status != DONE))} job(s) not DONE")
    return run_dir, result, (plat, wl, cfg), state


def phase_curie(out_dir: str, n_jobs: int = 10_000, nodes: int = 11_200,
                prefix: int = 300) -> dict:
    """The whole synthesized Curie trace through ``launch/sim.run`` on both
    event-pass routes, with an oracle check on a full-width prefix."""
    swf = synthesize_curie_swf(os.path.join(out_dir, "curie.swf"),
                               n_jobs=n_jobs)
    routes = (
        ("event_fuse_occ", curie_platform(nodes), True),
        ("event_fuse_ledger", nodes, False),
    )
    summary = {}
    # the oracle is host Python and the replay waits on the chip: the
    # prefix's oracle runs on a thread while the whole trace replays
    with ThreadPoolExecutor(max_workers=1) as pool:
        for route, platform, grouped in routes:
            short = replay_curie(swf, route, platform, nodes, prefix,
                                 grouped, out_dir)
            oracle = pool.submit(run_oracle, *short[2])
            _, _, inputs, state = replay_curie(
                swf, route, platform, nodes, n_jobs, grouped, out_dir
            )
            t0 = time.perf_counter()
            has_kernel = KERNEL_MARK in program_text(*inputs)
            print(f"[curie] route={route} program holds the Pallas kernel: "
                  f"{has_kernel} (recompiled in "
                  f"{time.perf_counter() - t0}s)", flush=True)
            if jax.default_backend() == "tpu":
                check(has_kernel, f"{route}: the compiled program has no "
                      f"{KERNEL_MARK}; the kernel route was not taken")
            rel = check_against_oracle(
                short[0], short[1], oracle.result(), f"curie {route} prefix"
            )
            print(f"[curie] route={route} prefix={prefix}: schedule "
                  f"bit-exact with the oracle, energy rel {rel:.3e} "
                  f"(oracle {oracle.result()[2]}s, on a thread)",
                  flush=True)
            summary[route] = {"n_batches": int(state.n_batches),
                              "kernel": has_kernel}
    return summary


def grid_experiment(out_dir: str, n_jobs: int, nodes: int):
    return experiments.Experiment(
        name="fig45_nasa_ipsc",
        workload={"preset": "nasa_ipsc", "n_jobs": n_jobs},
        platform=nodes,
        schedulers=GRID_SCHEDULERS,
        timeouts=GRID_TIMEOUTS,
        out=os.path.join(out_dir, "grid"),
    )


def check_grid_rows(exp, rows, sample) -> None:
    """Sampled grid rows agree with the oracle (``bench_energy --validate``)."""
    plat = experiments.resolve_platform(exp.platform)
    wl = experiments.resolve_workload(exp.workload)
    for i in sample:
        row = rows[i]
        base, pol = from_label(row["scheduler"])
        m_ref, _ = run_pydes(
            plat, wl, EngineConfig(base=base, policy=pol, timeout=row["timeout"])
        )
        what = f"grid row {i} ({row['scheduler']}, {row['timeout']}s)"
        check(row["makespan_s"] == m_ref.makespan_s,
              f"{what}: makespan {row['makespan_s']} vs {m_ref.makespan_s}")
        check(row["n_terminated"] == m_ref.n_terminated,
              f"{what}: n_terminated differs from the oracle")
        check(math.isclose(row["mean_wait_s"], m_ref.mean_wait_s,
                           rel_tol=1e-6, abs_tol=1e-6),
              f"{what}: mean wait {row['mean_wait_s']} vs {m_ref.mean_wait_s}")
        e = row["total_energy_kwh"] * 3.6e6
        check(math.isclose(e, m_ref.total_energy_j, rel_tol=ENERGY_RTOL),
              f"{what}: energy {e} J vs oracle {m_ref.total_energy_j} J")


def phase_grid(out_dir: str, n_jobs: int = 2000, nodes: int = 128,
               sample=(0, 11, 23)):
    """The Figs. 4/5 grid through the CLI's ``--experiment``: one program."""
    exp = grid_experiment(out_dir, n_jobs, nodes)
    os.makedirs(out_dir, exist_ok=True)
    spec = os.path.join(out_dir, "grid.json")
    exp.save(spec)
    with timed("grid", {"rows": len(GRID_SCHEDULERS) * len(GRID_TIMEOUTS)}):
        result = sim.main(["--experiment", spec])
    check(result.n_compiles == 1,
          f"the grid compiled {result.n_compiles} programs, not 1")
    check(len(result.rows) == len(GRID_SCHEDULERS) * len(GRID_TIMEOUTS),
          f"grid returned {len(result.rows)} rows")
    check(not any(r.get("truncated") for r in result.rows),
          "a grid row was truncated")
    t0 = time.perf_counter()
    check_grid_rows(exp, result.rows, sample)
    print(f"[grid] rows {list(sample)} agree with the oracle "
          f"({time.perf_counter() - t0}s)", flush=True)
    return result


def phase_serve(out_dir: str, n_jobs: int = 2000, nodes: int = 128) -> list:
    """Two same-shaped requests to the in-process service."""
    service = SimService(out_root=os.path.join(out_dir, "serve"))
    base = dict(
        workload={"preset": "nasa_ipsc", "n_jobs": n_jobs},
        platform=nodes,
        schedulers=("EASY PSUS", "FCFS PSAS"),
    )
    with timed("serve"):
        service.submit("user-a", experiments.Experiment(
            name="user-a", timeouts=(600, 1800), **base))
        service.submit("user-b", experiments.Experiment(
            name="user-b", timeouts=(900, 3600), **base))
        responses = service.drain()
    by_name = {r["request"]: r for r in responses}
    for r in responses:
        print(f"[serve] {json.dumps(r, sort_keys=True)}", flush=True)
    check(set(by_name) == {"user-a", "user-b"},
          f"service answered {sorted(by_name)}")
    for r in responses:
        check(r["status"] == "done", f"request {r['request']} failed: "
              f"{r.get('error')}")
        check(r["rows"] == 4, f"request {r['request']} returned "
              f"{r['rows']} rows")
    b = by_name["user-b"]
    check(b["compile_cache"] == {"hits": b["chunks"], "misses": 0},
          f"the second request recompiled: {b['compile_cache']}")
    return responses


def phase_rl(out_dir: str, nodes: int = 128, n_envs: int = 16,
             n_updates: int = 3, eval_workload: str = "preset:nasa_ipsc",
             devices=None, replay: bool = True) -> list:
    """A few A2C updates, then the saved policy replayed as ``EASY RL``."""
    from repro.core.rl.a2c import A2CConfig, train_a2c
    from repro.core.rl.env import EnvConfig
    from repro.training.checkpoint import save_policy
    from repro.workloads.generator import GeneratorConfig, generate_workload

    base, pol = from_label("EASY RL")
    env_cfg = EnvConfig(
        engine=EngineConfig(base=base, policy=pol, rl_decision_interval=600)
    )
    wls = [
        generate_workload(GeneratorConfig(
            n_jobs=48, nb_res=nodes, mean_interarrival=1500.0, seed=s))
        for s in range(n_envs)
    ]
    a2c = A2CConfig(n_envs=n_envs, n_steps=16, n_updates=n_updates)
    label = "rl_train" if devices is None else f"rl_train_{devices}dev"
    with timed(label, {"envs": n_envs, "updates": n_updates}):
        params, history = train_a2c(
            PlatformSpec(nb_nodes=nodes), wls, env_cfg, a2c, devices=devices
        )
    losses = [h["loss"] for h in history]
    print(f"[{label}] losses={losses}", flush=True)
    check(len(losses) == n_updates and all(map(math.isfinite, losses)),
          f"A2C losses not finite: {losses}")
    if not replay:
        return history
    ckpt = os.path.join(out_dir, "rl_policy")
    save_policy(
        ckpt, params, obs_size=env_cfg.obs_size, n_actions=env_cfg.n_actions,
        feature=env_cfg.feature, action=env_cfg.action,
        n_levels=env_cfg.n_action_levels, hidden=a2c.hidden,
        feature_window=env_cfg.feature_window,
    )
    run_dir = os.path.join(out_dir, "rl_run")
    cfg_path = os.path.join(out_dir, "rl_run.json")
    # no Gantt log: the paper run covers that path, whose PNG is drawn one
    # bar per interval on the host (about 100 s of a 2,000-job run's wall
    # time on a TPU v5e host)
    with open(cfg_path, "w") as f:
        json.dump({
            "workload": eval_workload, "platform": nodes,
            "scheduler": "EASY RL", "timeout": None, "gantt": False,
            "rl": {"checkpoint": ckpt, "decision_interval": 600},
            "out": run_dir,
        }, f)
    with timed("rl_run"):
        result = sim.main(["--config", cfg_path])
    check(not result.get("truncated", False), "RL run was truncated")
    check(result["n_jobs"] == len(experiments.resolve_workload(eval_workload)),
          f"RL run finished {result['n_jobs']} jobs")
    check(math.isfinite(result["total_energy_kwh"]),
          "RL run energy is not finite")
    return history


def phase_sharded_grid(out_dir: str, devices: int = 4, n_jobs: int = 2000,
                       nodes: int = 128):
    """The step-4 grid sharded over ``devices`` against one device."""
    exp = grid_experiment(out_dir, n_jobs, nodes)
    with timed("grid_1dev"):
        one = experiments.run(exp)
    with timed(f"grid_{devices}dev"):
        many = experiments.run(exp, devices=devices)
    check(many.n_compiles == 1,
          f"the sharded grid compiled {many.n_compiles} programs, not 1")
    a = json.dumps(one.rows, sort_keys=True)
    b = json.dumps(many.rows, sort_keys=True)
    check(a == b, f"{devices}-device rows differ from the 1-device rows")
    print(f"[grid_{devices}dev] {len(many.rows)} rows byte-identical to the "
          f"1-device run, n_compiles={many.n_compiles}", flush=True)
    return many


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the simulator's main paths on the TPU and check them."
    )
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device sharded grid and A2C paths")
    args = ap.parse_args(argv)
    use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    os.makedirs(OUT, exist_ok=True)

    if args.four_chips:
        device = phase_device(min_count=4)
        phase_sharded_grid(OUT, devices=4)
        phase_rl(OUT, devices=4, replay=False)
    else:
        device = phase_device()
        phase_paper_run(OUT)
        phase_curie(OUT)
        phase_grid(OUT)
        phase_serve(OUT)
        phase_rl(OUT)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
