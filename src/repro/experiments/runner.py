"""Evaluate an :class:`Experiment`: the whole grid, one compiled program.

``run(experiment)`` resolves the spec, builds the scheduler x timeout
scenario grid, and pushes it through ``engine.sweep`` — the traced policy
axis makes the full grid (all replications included) exactly ONE compiled
XLA program. A single-point grid (1 scheduler x 1 timeout) skips the
superset program entirely and takes ``engine.simulate``'s statically
specialized path instead: the policy flags are closure constants, dead
rules are DCE'd, and the compile is cached across replications/reruns
(core/SEMANTICS.md §Static specialization) — rows are bit-exact either
way. Results come back as a flat rows table (one dict per grid
point per replication) and, when ``experiment.out`` is set, are written as
a deterministic ``metrics.json`` (byte-identical across reruns of the same
spec — the golden-file anchor in ``tests/test_experiments.py``) plus a
``rows.csv`` for spreadsheet use.

``run(..., stream=True)`` swaps the barrier for a :class:`StreamingRun`
iterator of completed row-chunks (core/SEMANTICS.md §Device-sharded
sweeps): the grid is chunked (``chunk_scenarios``), chunk ``k+1`` is
dispatched through ``engine.sweep_async`` before chunk ``k``'s host
transfer drains, and ``metrics.json``/``rows.csv`` are rewritten after
every chunk — incremental progress on disk, yet the final files are
byte-identical to the blocking path. ``devices`` shards each launch's
scenario axis across local devices (bit-exact either way).
"""
from __future__ import annotations

import csv
import dataclasses
import json
import os
import time
import warnings
from collections import deque
from typing import Any, Iterator, Optional, Tuple

from repro.core import engine, spans
from repro.experiments.spec import Experiment, resolve_platform, resolve_workload


@dataclasses.dataclass(frozen=True)
class ExperimentResult:
    """Rows are scheduler-major x timeout [x forecast] [x platform] x
    replication, in grid order (``forecast`` / ``platform`` columns appear
    when the spec has those axes).

    ``n_compiles`` is the compiled-program count of the grid's jitted
    driver (the one-compile guarantee: 1, or None on JAX versions without
    cache introspection). ``wall_s`` is host wall time for all sweeps —
    reported, never written into metrics.json (determinism).
    """

    experiment: Experiment
    rows: Tuple[dict, ...]
    n_compiles: Optional[int]
    wall_s: float

    @property
    def jobs_per_s(self) -> float:
        sim_jobs = sum(r["n_jobs"] for r in self.rows)
        return sim_jobs / self.wall_s if self.wall_s > 0 else 0.0

    def table(self) -> str:
        """A compact fixed-width text table (CLI output)."""
        cols = ["scheduler", "timeout", "replication", "total_energy_kwh",
                "wasted_energy_kwh", "mean_wait_s", "utilization"]
        if any("platform" in r for r in self.rows):
            cols.insert(2, "platform")
        if any("forecast" in r for r in self.rows):
            cols.insert(2, "forecast")
        lines = [" ".join(f"{c:>18s}" for c in cols)]
        for r in self.rows:
            cells = []
            for c in cols:
                v = r.get(c)
                cells.append(
                    f"{v:>18.3f}" if isinstance(v, float) else f"{str(v):>18s}"
                )
            lines.append(" ".join(cells))
        return "\n".join(lines)


def _metrics_payload(result: ExperimentResult) -> dict:
    return {
        "experiment": dataclasses.asdict(result.experiment),
        "n_compiles": result.n_compiles,
        "rows": list(result.rows),
    }


def _engine_config_with_rl(experiment: Experiment, plat):
    """The shared static EngineConfig; RL scheduler labels get the
    checkpointed in-graph controller from ``experiment.rl`` attached.

    The controller is static trace structure (core/SEMANTICS.md §Traced vs
    static), shared by every grid point: non-RL rows run it with rule 8
    traced off, and all RL labels must therefore name ONE policy stack.
    """
    from repro.core.policy import RLController, from_label

    cfg = experiment.engine_config()
    rl_stacks = {
        label: pol
        for label in experiment.schedulers
        for _, pol in [from_label(label)]
        if isinstance(pol, RLController)
    }
    if not rl_stacks:
        if experiment.rl is not None:
            raise ValueError(
                "experiment declares an rl checkpoint block but no RL "
                f"scheduler label is in the grid ({list(experiment.schedulers)}); "
                "add an 'RL' / 'RL:groups' / 'RL:dvfs' label or drop the "
                "rl entry"
            )
        return cfg
    if len(set(rl_stacks.values())) > 1:
        raise ValueError(
            "an experiment grid shares ONE in-graph RL controller (static "
            "trace structure); scheduler labels "
            f"{sorted(rl_stacks)} name different RL stacks — split them "
            "into separate experiments"
        )
    if not experiment.rl or "checkpoint" not in experiment.rl:
        raise ValueError(
            f"RL scheduler label(s) {sorted(rl_stacks)} need an "
            'rl: {"checkpoint": <dir>} experiment entry (a policy saved by '
            "training.checkpoint.save_policy)"
        )
    # lazy import: repro.launch.sim imports repro.experiments at module top
    from repro.launch.sim import _resolve_rl_policy

    pol = next(iter(rl_stacks.values()))
    pol, rl = _resolve_rl_policy(pol, {"rl": dict(experiment.rl)}, plat)
    return dataclasses.replace(
        cfg,
        policy=pol,
        rl_decision_interval=rl.get("decision_interval"),
    )


def _run_single(plat, wl, scenario, cfg):
    """One grid point through the specialized single-config program.

    The scenario dict is the grid() shape ({scheduler, timeout[, platform
    -> resolved PlatformSpec]}); the label's policy point is folded into
    the trace as closure constants (``engine.simulate`` specialization),
    bit-exact with the traced sweep row it replaces. Returns
    (SimMetrics, n_compiles-of-the-cached-program).
    """
    from repro.core.metrics import metrics_from_state
    from repro.core.policy import RLController, from_label

    base, pol = from_label(scenario["scheduler"])
    if isinstance(pol, RLController):
        # cfg.policy carries the checkpointed in-graph controller attached
        # by _engine_config_with_rl (shared static trace structure)
        pol = cfg.policy
    plat_i = scenario.get("platform", plat)
    cfg_i = dataclasses.replace(
        cfg,
        base=base,
        policy=pol,
        timeout=scenario["timeout"],
        forecast_horizon=scenario.get("forecast_horizon", cfg.forecast_horizon),
    )
    state, n = engine.simulate(plat_i, wl, cfg_i, return_compiles=True)
    return metrics_from_state(state, plat_i), n


def _row(sc: dict, replication: int, m) -> dict:
    """One rows-table entry for grid point ``sc`` (the declarative dict,
    platform still a *name*) — shared by the blocking and streaming paths
    so their rows are identical by construction."""
    row = {
        "scheduler": sc["scheduler"],
        "timeout": sc["timeout"],
    }
    if "forecast" in sc:
        row["forecast"] = sc["forecast"]
    if "platform" in sc:
        row["platform"] = sc["platform"]
    row["replication"] = replication
    row.update(m.row())
    return row


def _warn_capped(rows) -> None:
    capped = [(r["scheduler"], r["timeout"]) for r in rows if r.get("truncated")]
    if capped:
        warnings.warn(
            f"experiment grid point(s) {capped} hit the batch cap before "
            "completing — their rows describe PARTIAL simulations "
            "('truncated' column). Raise max_batches to run to completion.",
            RuntimeWarning,
            stacklevel=3,
        )


def _resolve_run(experiment: Experiment, platform, workload):
    """Shared spec resolution for the blocking and streaming paths:
    validate the injection rules, resolve platform + engine config, and
    lower the declarative grid to traced sweep scenarios. Returns
    ``(plat, cfg, grid, scenarios)`` with ``grid`` keeping the
    platform-axis *names* for the rows table."""
    if workload is not None and experiment.replications > 1:
        raise ValueError(
            "cannot inject a workload into a run with replications > 1: "
            "replications >= 1 regenerate from the spec's workload entry, "
            "which need not match the injected object"
        )
    if experiment.out and (platform is not None or workload is not None):
        raise ValueError(
            "cannot combine injected platform/workload objects with "
            "experiment.out: metrics.json records the spec as the "
            "reproduction recipe, which would not describe what actually "
            "ran; write outputs yourself or put the platform/workload in "
            "the spec"
        )
    plat = platform if platform is not None else resolve_platform(experiment.platform)
    cfg = _engine_config_with_rl(experiment, plat)
    # swap platform-axis *names* for resolved PlatformSpecs (traced sweep
    # scenarios); the declarative grid keeps the names for the rows table
    grid = experiment.grid()
    axis = {name: resolve_platform(spec) for name, spec in experiment.platforms}
    scenarios = []
    for sc in grid:
        sc = dict(sc)
        if "platform" in sc:
            sc["platform"] = axis[sc["platform"]]
        if "forecast" in sc:
            # the declarative forecast axis lowers to the traced
            # EngineConst.forecast_horizon operand (§Forecast) — the raw
            # field-override branch of engine.sweep's scenario mapping
            sc["forecast_horizon"] = sc.pop("forecast")
        scenarios.append(sc)
    return plat, cfg, grid, scenarios


def _workload(experiment: Experiment, workload, replication: int):
    """Replication ``replication``'s workload: the injected one (which
    implies ``replications == 1``, guarded in ``_resolve_run``), else the
    spec's, resolved (an SWF file is parsed here)."""
    if workload is not None:
        return workload
    with spans.span("experiments.workload"):
        return resolve_workload(experiment.workload, replication=replication)


class StreamingRun:
    """Iterator of completed row-chunks from ``run(..., stream=True)``.

    Each ``next()`` blocks only until the *oldest* in-flight chunk's device
    work lands on the host, then yields that chunk's rows (a tuple of row
    dicts, grid order); the next chunk was already dispatched, so device
    compute overlaps the host-side consumption of earlier chunks. After
    exhaustion ``result`` holds the final :class:`ExperimentResult` —
    identical (and, via ``experiment.out``, byte-identical on disk) to what
    the blocking path returns.
    """

    def __init__(self, gen: Iterator[Tuple[dict, ...]]):
        self._gen = gen
        self.result: Optional[ExperimentResult] = None

    def __iter__(self) -> "StreamingRun":
        return self

    def __next__(self) -> Tuple[dict, ...]:
        return next(self._gen)


def run(
    experiment: Experiment,
    platform=None,
    workload=None,
    *,
    devices: Optional[Any] = None,
    stream: bool = False,
    chunk_scenarios: Optional[int] = None,
) -> ExperimentResult:
    """Run the experiment grid; one compiled program for everything.

    ``platform`` / ``workload`` optionally inject pre-resolved objects
    (benchmarks construct platforms programmatically); the spec remains the
    declarative record. With both injected and ``replications == 1`` the
    spec's workload/platform entries are never resolved. A workload can only
    be injected into a single-replication run: replications r >= 1 would be
    resolved from the spec, silently mixing two different studies.

    ``devices`` shards each sweep launch's scenario axis across local
    devices (``engine.sweep``'s contract: None/int/"all", bit-exact
    regardless; the single-point fast path runs one simulation and is
    never sharded). ``stream=True`` returns a :class:`StreamingRun`
    instead of blocking on the whole grid; ``chunk_scenarios`` bounds the
    scenarios per launch (default: the whole grid per replication).
    """
    if stream:
        return _run_stream(
            experiment,
            platform,
            workload,
            devices=devices,
            chunk_scenarios=chunk_scenarios,
        )
    if chunk_scenarios is not None:
        raise ValueError(
            "chunk_scenarios only applies to stream=True: the blocking "
            "path runs the whole grid as one launch (its one-compile / "
            "one-dispatch shape is the point)"
        )
    plat, cfg, grid, scenarios = _resolve_run(experiment, platform, workload)

    rows = []
    n_compiles: Optional[int] = None
    t0 = time.perf_counter()
    for r in range(experiment.replications):
        wl = _workload(experiment, workload, r)
        with warnings.catch_warnings():
            # the engine layers warn per call; run() emits ONE aggregated
            # warning over the rows below, labelled with the grid points
            warnings.filterwarnings(
                "ignore", message=".*batch cap.*", category=RuntimeWarning
            )
            if len(scenarios) == 1:
                # single-point grid: the statically-specialized fast path
                # (one cached compile per config, dead rules DCE'd) instead
                # of the traced-superset sweep program — bit-exact either way
                metrics, n = _run_single(plat, wl, scenarios[0], cfg)
                batch_metrics = (metrics,)
            else:
                batch = engine.sweep(plat, wl, scenarios, cfg, devices=devices)
                batch_metrics, n = batch.metrics, batch.n_compiles
        if n is not None:
            n_compiles = max(n_compiles or 0, n)
        for sc, m in zip(grid, batch_metrics):
            rows.append(_row(sc, r, m))
    wall = time.perf_counter() - t0
    _warn_capped(rows)

    result = ExperimentResult(
        experiment=experiment,
        rows=tuple(rows),
        n_compiles=n_compiles,
        wall_s=wall,
    )
    if experiment.out:
        write_outputs(result, experiment.out)
    return result


# in-flight launches per StreamingRun: chunk k+1 is dispatched before chunk
# k's transfer drains (device compute overlaps host consumption); deeper
# pipelines buy nothing on one host and hold more device memory live
_STREAM_DEPTH = 2


def _run_stream(
    experiment: Experiment,
    platform,
    workload,
    *,
    devices: Optional[Any],
    chunk_scenarios: Optional[int],
) -> StreamingRun:
    """``run(..., stream=True)``: the same grid as launches of at most
    ``chunk_scenarios`` scenarios through ``engine.sweep_async``, yielded
    chunk-by-chunk as each lands. Rows, aggregated warning, final
    ExperimentResult, and (when ``experiment.out`` is set) the final
    ``metrics.json``/``rows.csv`` bytes are identical to the blocking path
    — the outputs are additionally REWRITTEN with rows-so-far after every
    chunk, so a crashed or abandoned stream leaves a valid prefix on disk.
    """
    plat, cfg, grid, scenarios = _resolve_run(experiment, platform, workload)
    chunk = chunk_scenarios if chunk_scenarios is not None else len(scenarios)
    if chunk < 1:
        raise ValueError(f"chunk_scenarios must be >= 1, got {chunk_scenarios!r}")
    single = len(scenarios) == 1

    holder = StreamingRun(iter(()))

    def gen():
        rows = []
        n_compiles: Optional[int] = None
        t0 = time.perf_counter()
        # (grid slice, replication, kind, payload) in dispatch order; rows
        # drain oldest-first so the table order matches the blocking path
        pending: deque = deque()

        def drain() -> Tuple[dict, ...]:
            nonlocal n_compiles
            grid_sl, r, kind, payload = pending.popleft()
            with warnings.catch_warnings():
                # per-launch truncation warnings surface at result() time;
                # aggregate them into the one labelled warning at the end
                warnings.filterwarnings(
                    "ignore", message=".*batch cap.*", category=RuntimeWarning
                )
                if kind == "single":
                    # single-point grid: the same statically-specialized
                    # path the blocking run takes (bit-exact rows); it
                    # computes synchronously here, at drain time
                    m, n = _run_single(plat, payload, scenarios[0], cfg)
                    batch_metrics = (m,)
                else:
                    batch = payload.result()
                    batch_metrics, n = batch.metrics, batch.n_compiles
            if n is not None:
                n_compiles = max(n_compiles or 0, n)
            chunk_rows = tuple(
                _row(sc, r, m) for sc, m in zip(grid_sl, batch_metrics)
            )
            rows.extend(chunk_rows)
            if experiment.out:
                # incremental rewrite with rows-so-far: always a valid
                # prefix; the last rewrite (all rows, n_compiles settled)
                # is byte-identical to the blocking path's single write
                write_outputs(
                    ExperimentResult(
                        experiment=experiment,
                        rows=tuple(rows),
                        n_compiles=n_compiles,
                        wall_s=time.perf_counter() - t0,
                    ),
                    experiment.out,
                )
            return chunk_rows

        for r in range(experiment.replications):
            wl = _workload(experiment, workload, r)
            if single:
                pending.append((grid, r, "single", wl))
                while len(pending) > _STREAM_DEPTH:
                    yield drain()
                continue
            for lo in range(0, len(scenarios), chunk):
                handle = engine.sweep_async(
                    plat, wl, scenarios[lo : lo + chunk], cfg, devices=devices
                )
                pending.append((grid[lo : lo + chunk], r, "sweep", handle))
                while len(pending) > _STREAM_DEPTH:
                    yield drain()
        while pending:
            yield drain()

        wall = time.perf_counter() - t0
        _warn_capped(rows)
        result = ExperimentResult(
            experiment=experiment,
            rows=tuple(rows),
            n_compiles=n_compiles,
            wall_s=wall,
        )
        if experiment.out:
            write_outputs(result, experiment.out)
        holder.result = result

    holder._gen = gen()
    return holder


def write_outputs(result: ExperimentResult, out_dir: str) -> None:
    with spans.span("experiments.write"):
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "metrics.json"), "w") as f:
            json.dump(_metrics_payload(result), f, indent=2, sort_keys=True)
            f.write("\n")
        rows = result.rows
        lead = ["scheduler", "timeout", "forecast", "platform", "replication"]
        cols = sorted({k for r in rows for k in r}, key=lambda c: (
            lead.index(c) if c in lead else len(lead),
            c,
        ))
        with open(os.path.join(out_dir, "rows.csv"), "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=cols)
            w.writeheader()
            w.writerows(rows)


def run_file(path: str) -> ExperimentResult:
    """CLI entry: load a spec file and run it (``launch/sim.py --experiment``)."""
    return run(Experiment.load(path))
