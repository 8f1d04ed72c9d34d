"""Benchmark entry point: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick defaults
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sizes
    PYTHONPATH=src python -m benchmarks.run --sections dvfs,rl   # a subset

Besides the console report, writes machine-readable ``BENCH_grid.json``
(per-section wall time, compile count, simulated jobs/s where applicable)
so the performance trajectory is tracked across PRs. With ``--sections``,
untouched sections of an existing report file are preserved (read-modify-
write), so one section can be refreshed without a full rerun.
"""
from __future__ import annotations

import argparse
import json
import os
import time

SECTIONS = ("speedup", "energy_grid", "fig1", "scale", "curie", "rl",
            "dvfs", "forecast")


def section(title):
    print(f"\n=== {title} " + "=" * max(0, 60 - len(title)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--out", default="BENCH_grid.json",
                    help="machine-readable per-section results")
    ap.add_argument(
        "--sections", default=None,
        help=f"comma-separated subset of {','.join(SECTIONS)}; other "
             "sections of an existing report are preserved",
    )
    args = ap.parse_args()
    t0 = time.time()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from benchmarks import (
        bench_curie,
        bench_dvfs,
        bench_energy,
        bench_forecast,
        bench_rl,
        bench_scale,
        bench_speedup,
    )

    if args.sections:
        wanted = set(args.sections.split(","))
        unknown = wanted - set(SECTIONS)
        if unknown:
            ap.error(f"unknown section(s) {sorted(unknown)}; "
                     f"known: {', '.join(SECTIONS)}")
    else:
        wanted = set(SECTIONS)

    report = {"full": bool(args.full), "sections": {}}
    if args.sections and os.path.exists(args.out):
        with open(args.out) as f:
            prior = json.load(f)
        if prior.get("full", False) != bool(args.full):
            ap.error(
                f"--sections would merge full={bool(args.full)} numbers "
                f"into a full={prior.get('full', False)} report ({args.out}); "
                "rerun without --sections or delete the report first"
            )
        report["sections"] = prior.get("sections", {})

    def want(name):
        return name in wanted

    # device header stamped into every section (core/SEMANTICS.md
    # §Device-sharded sweeps): numbers measured on 1 CPU device and on a
    # forced-8-device host (or a real accelerator mesh) are not comparable,
    # so the report says which machine shape produced each section
    import jax

    device_header = {
        "devices": jax.device_count(),
        "backend": jax.default_backend(),
        "sharded": jax.device_count() > 1,
    }

    def timed(name, fn, **extra):
        s0 = time.perf_counter()
        ret = fn()
        entry = {
            "wall_s": round(time.perf_counter() - s0, 3),
            **device_header,
            **extra,
        }
        report["sections"][name] = entry
        return ret, entry

    if want("speedup"):
        section("Table 4: engine speedup vs sequential oracle (CIEMAT)")
        speedup_jobs = 1000 if args.full else 300
        timed(
            "speedup",
            lambda: bench_speedup.main(["--jobs", str(speedup_jobs)]),
            jobs=speedup_jobs,
        )

    if want("energy_grid"):
        section("Figs. 4/5: six schedulers x timeout grid (NASA) + validation")
        energy_jobs = 2000 if args.full else 300

        def run_energy():
            return bench_energy.main(
                ["--jobs", str(energy_jobs), "--timeouts", "5,15,30,60",
                 "--validate"]
            )

        (rows, grid_result), entry = timed("energy_grid", run_energy)
        entry.update(
            n_compiles=grid_result.n_compiles,
            grid_rows=len(rows),
            jobs_per_s=round(grid_result.jobs_per_s, 1),
            max_energy_dev=max(r["energy_dev"] for r in rows),
        )

    if want("fig1"):
        section("Fig. 1: same-time batching divergence")
        timed("fig1", lambda: bench_energy.main(["--fig1"]))

    if want("scale"):
        section("CEA-Curie scale (11200 nodes)")

        def run_scale():
            return bench_scale.main(
                ["--jobs", "1000" if args.full else "200",
                 "--sweep", "8" if args.full else "4"]
            )

        scale, entry = timed("scale", run_scale)
        entry.update(
            n_compiles=scale.get("n_compiles"),
            grid_k=scale.get("grid_k"),
            jobs_per_s=round(
                scale["grid_k"] * scale["jobs"] / scale["t_sweep"], 1
            ) if scale.get("t_sweep") else None,
            single_run_s=round(scale["t_jax"], 3),
            single_run_specialized_s=round(scale["t_jax_spec"], 3),
            single_run_fused_s=round(scale["t_jax_fused"], 3),
            single_run_grouped_s=round(scale["t_jax_grouped"], 3),
            oracle_run_s=round(scale["t_oracle"], 3),
        )
        if "t_sweep_sharded" in scale:
            entry.update(
                sweep_sharded_s=round(scale["t_sweep_sharded"], 3),
                sweep_devices=scale["sweep_devices"],
            )

    if want("curie"):
        section("Curie-scale SWF trace replay (group-indexed tables)")

        def run_curie():
            return bench_curie.main(
                ["--jobs", "10000", "--verify-jobs",
                 "120" if not args.full else "300"]
                + (["--full"] if args.full else [])
            )

        curie, entry = timed("curie", run_curie)
        entry.update(
            trace_jobs=curie["trace_jobs"],
            bench_jobs=curie["bench_jobs"],
            nodes=curie["nodes"],
            n_groups=curie["n_groups"],
            verify_labels=curie["verify_labels"],
            single_run_dense_fused_s=round(curie["t_dense_fused"], 3),
            single_run_grouped_s=round(curie["t_grouped"], 3),
            single_run_grouped_merge_s=round(curie["t_grouped_merge"], 3),
        )
        if "t_full_replay_grouped" in curie:
            entry.update(
                full_replay_grouped_s=round(curie["t_full_replay_grouped"], 3),
                full_replay_jobs=curie["full_replay_jobs"],
            )

    if want("rl"):
        section("RL workflow throughput")
        rl, entry = timed(
            "rl",
            lambda: bench_rl.main(
                ["--envs", "256" if args.full else "64",
                 "--steps", "64" if args.full else "16"]
            ),
        )
        if isinstance(rl, dict):
            entry.update(
                {f"steps_per_s_{k}": round(v, 1) for k, v in rl.items()}
            )

    if want("dvfs"):
        section("Runtime DVFS: scheduler x mode-table grid (one compile)")
        dvfs_jobs = 1000 if args.full else 300
        dvfs, entry = timed(
            "dvfs", lambda: bench_dvfs.main(["--jobs", str(dvfs_jobs)])
        )
        entry.update(
            n_compiles=dvfs.get("n_compiles"),
            grid_k=dvfs.get("grid_k"),
            jobs_per_s=dvfs.get("jobs_per_s"),
        )

    if want("forecast"):
        section("Rule 10: reactive vs +Forecast vs RL:groups (Curie head)")
        fc_jobs = 200 if args.full else 120
        fc_nodes = 280 if args.full else 120
        fc, entry = timed(
            "forecast",
            lambda: bench_forecast.main(
                ["--jobs", str(fc_jobs), "--nodes", str(fc_nodes),
                 "--trace", "2000" if args.full else "600"]
            ),
        )
        entry.update(
            n_compiles=fc.get("n_compiles"),
            grid_k=fc.get("grid_k"),
            nodes=fc.get("nodes"),
            bench_jobs=fc.get("bench_jobs"),
            jobs_per_s=fc.get("jobs_per_s"),
        )

    # total is the sum of the recorded sections (consistent under
    # --sections merges, where this run's wall time covers only a subset)
    report["total_wall_s"] = round(
        sum(sec.get("wall_s", 0.0) for sec in report["sections"].values()), 1
    )
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"\nbenchmarks done in {time.time() - t0:.0f}s "
          f"(sections total {report['total_wall_s']:.0f}s; "
          f"machine-readable report -> {args.out})")


if __name__ == "__main__":
    main()
