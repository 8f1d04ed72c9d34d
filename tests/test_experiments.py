"""Declarative experiment layer + the traced policy axis.

Covers: Experiment JSON round-trip (incl. golden-file determinism of
metrics.json across reruns), the flag-gated superset program's
bit-exactness vs per-config compiles AND the sequential oracle on
fig3_small for all six scheduler labels, one-compile grids (tier-1 small;
the full 6x4 nightly grid is the `slow` lane asserted by
`make test-nightly`), and the launch CLI --experiment path.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro import experiments
from repro.core import engine
from repro.core.metrics import metrics_from_state, schedule_table
from repro.core.policy import (
    IPM,
    PolicyParams,
    TimeoutSleep,
    from_label,
    scheduler_labels,
)
from repro.core.ref.pydes import run_pydes
from repro.core.types import BasePolicy, EngineConfig
from repro.workloads.generator import PRESETS, GeneratorConfig, generate_workload
from repro.workloads.platform import PlatformSpec

SIX = tuple(l for l in scheduler_labels() if "AlwaysOn" not in l)


# --------------------------------------------------------------- spec layer

def test_experiment_json_roundtrip():
    exp = experiments.Experiment(
        name="rt",
        workload={"preset": "fig3_small", "n_jobs": 40},
        platform=16,
        schedulers=SIX,
        timeouts=(60, 300, None),
        node_order="cheap",
        terminate_overrun=True,
        replications=2,
        out="out/rt",
    )
    again = experiments.Experiment.from_json(exp.to_json())
    assert again == exp
    # tuples normalize from JSON lists; grid order is scheduler-major
    assert again.schedulers == SIX
    assert again.grid()[0] == {"scheduler": SIX[0], "timeout": 60}
    assert len(again.grid()) == len(SIX) * 3


def test_experiment_rejects_bad_specs():
    with pytest.raises(ValueError, match="did you mean 'schedulers'"):
        experiments.Experiment.from_json(
            json.dumps(
                {"name": "x", "workload": "preset:fig3_small",
                 "platform": 16, "scheduler": ["EASY PSUS"]}
            )
        )
    with pytest.raises(KeyError, match="unknown scheduler label"):
        experiments.Experiment(
            name="x", workload="preset:fig3_small", platform=16,
            schedulers=("EASY TURBO",),
        )
    with pytest.raises(ValueError, match=">= 1 scheduler"):
        experiments.Experiment(
            name="x", workload="preset:fig3_small", platform=16,
            schedulers=(),
        )
    with pytest.raises(ValueError, match="replications"):
        experiments.Experiment(
            name="x", workload="preset:fig3_small", platform=16,
            replications=0,
        )
    with pytest.raises(ValueError, match="seed"):
        # a file-backed workload has no seed axis to replicate over
        experiments.resolve_workload("profiles", replication=1)
    with pytest.raises(ValueError, match="did you mean 'n_jobs'"):
        # typo'd generator-override keys fail at spec construction, not as
        # an opaque dataclasses.replace TypeError at run() time
        experiments.Experiment(
            name="x", platform=16,
            workload={"preset": "fig3_small", "n_job": 40},
        )


def test_run_rejects_injection_that_breaks_the_record(tmp_path):
    """Injected platform/workload objects cannot be combined with spec
    outputs (metrics.json records the spec as the reproduction recipe) or
    with replications > 1 (r >= 1 regenerates from the spec)."""
    exp = experiments.Experiment(
        name="inj", workload={"preset": "fig3_small", "n_jobs": 10},
        platform=8,
    )
    wl = experiments.resolve_workload(exp.workload)
    with pytest.raises(ValueError, match="reproduction recipe"):
        experiments.run(
            dataclasses.replace(exp, out=str(tmp_path)), workload=wl
        )
    with pytest.raises(ValueError, match="replications"):
        experiments.run(
            dataclasses.replace(exp, replications=2), workload=wl
        )


def test_experiment_golden_file_run(tmp_path):
    """load -> run -> metrics.json; rerun of the identical spec produces a
    byte-identical metrics.json (the golden-file anchor: seeded generator +
    one compiled program + deterministic f32 ledger)."""
    spec_path = tmp_path / "exp.json"
    out = tmp_path / "out"
    experiments.Experiment(
        name="golden",
        workload={"preset": "fig3_small", "n_jobs": 50},
        platform=16,
        schedulers=("EASY PSUS", "FCFS PSAS"),
        timeouts=(120, None),
        terminate_overrun=True,
        out=str(out),
    ).save(str(spec_path))

    result = experiments.run_file(str(spec_path))
    assert len(result.rows) == 4
    if result.n_compiles is not None:
        assert result.n_compiles == 1
    with open(out / "metrics.json") as f:
        first = f.read()
    payload = json.loads(first)
    assert payload["experiment"]["name"] == "golden"
    assert [r["scheduler"] for r in payload["rows"]] == [
        "EASY PSUS", "EASY PSUS", "FCFS PSAS", "FCFS PSAS"
    ]
    assert os.path.exists(out / "rows.csv")

    experiments.run_file(str(spec_path))  # golden rerun
    with open(out / "metrics.json") as f:
        assert f.read() == first


def test_replications_advance_the_seed():
    exp = experiments.Experiment(
        name="reps",
        workload={"preset": "fig3_small", "n_jobs": 30},
        platform=16,
        schedulers=("EASY PSUS",),
        timeouts=(300,),
        replications=2,
    )
    result = experiments.run(exp)
    r0, r1 = result.rows
    assert r0["replication"] == 0 and r1["replication"] == 1
    assert r0["total_energy_kwh"] != r1["total_energy_kwh"]


# ------------------------------------------- superset program bit-exactness

@pytest.mark.parametrize("label", SIX)
def test_superset_bit_exact_per_label_fig3(label):
    """The flag-gated superset program vs a per-config compile vs the
    sequential oracle, on fig3_small, for every paper scheduler label:
    schedule tables bit-exact both ways, f32 energy ledger bit-exact vs the
    per-config compile, f64-oracle energy within the Kahan tolerance."""
    wl = generate_workload(
        GeneratorConfig(**{**PRESETS["fig3_small"].__dict__, "n_jobs": 80})
    )
    plat = PlatformSpec(nb_nodes=16)
    cfg = EngineConfig(terminate_overrun=True)
    batch = engine.sweep(
        plat, wl, [{"scheduler": label, "timeout": 180}], cfg
    )
    state = batch.state_at(0)

    base, pol = from_label(label)
    single_cfg = EngineConfig(
        base=base, policy=pol, timeout=180, terminate_overrun=True
    )
    single = engine.simulate(plat, wl, single_cfg)
    np.testing.assert_array_equal(schedule_table(state), schedule_table(single))
    np.testing.assert_array_equal(
        np.asarray(state.energy), np.asarray(single.energy)
    )

    m_ref, des = run_pydes(plat, wl, single_cfg)
    np.testing.assert_array_equal(schedule_table(state), des.schedule_table())
    m = metrics_from_state(state, plat)
    assert m.total_energy_j == pytest.approx(m_ref.total_energy_j, rel=1e-5)
    assert m.makespan_s == m_ref.makespan_s


def test_grid_one_compile_small():
    """6 schedulers x 2 timeouts: ONE compiled program, every row bit-exact
    with its per-config compile (the tier-1 sampling of the nightly 6x4
    assertion)."""
    wl = generate_workload(
        GeneratorConfig(**{**PRESETS["fig3_small"].__dict__, "n_jobs": 60})
    )
    plat = PlatformSpec(nb_nodes=16)
    cfg = EngineConfig(terminate_overrun=True, window=32)
    scenarios = [
        {"scheduler": lbl, "timeout": t} for lbl in SIX for t in (90, 600)
    ]
    batch = engine.sweep(plat, wl, scenarios, cfg)
    if batch.n_compiles is not None:
        assert batch.n_compiles == 1
    for i, sc in enumerate(scenarios):
        base, pol = from_label(sc["scheduler"])
        single = engine.simulate(
            plat, wl,
            EngineConfig(base=base, policy=pol, timeout=sc["timeout"],
                         terminate_overrun=True),
        )
        np.testing.assert_array_equal(
            schedule_table(batch.state_at(i)), schedule_table(single),
            err_msg=str(sc),
        )
        np.testing.assert_array_equal(
            np.asarray(batch.state_at(i).energy), np.asarray(single.energy),
            err_msg=str(sc),
        )


@pytest.mark.slow
def test_nightly_full_grid_one_compile():
    """The acceptance grid: 6 schedulers x 4 timeouts through the
    experiment layer, n_compiles == 1, with per-row oracle parity on a
    sample of rows (`make test-nightly`)."""
    exp = experiments.Experiment(
        name="nightly_grid",
        workload={"preset": "fig3_small", "n_jobs": 120},
        platform=16,
        schedulers=SIX,
        timeouts=(60, 300, 900, 1800),
        terminate_overrun=True,
    )
    result = experiments.run(exp)
    assert len(result.rows) == 24
    assert result.n_compiles in (None, 1), (
        f"full grid recompiled: {result.n_compiles} programs"
    )
    wl = experiments.resolve_workload(exp.workload)
    plat = experiments.resolve_platform(exp.platform)
    for row in result.rows[:: 6]:
        base, pol = from_label(row["scheduler"])
        m_ref, _ = run_pydes(
            plat, wl,
            EngineConfig(base=base, policy=pol, timeout=row["timeout"],
                         terminate_overrun=True),
        )
        assert row["total_energy_kwh"] * 3.6e6 == pytest.approx(
            m_ref.total_energy_j, rel=1e-5
        ), row["scheduler"]


# ----------------------------------------------------- policy-axis plumbing

def test_policy_params_lowering():
    assert TimeoutSleep().params(BasePolicy.EASY) == PolicyParams(
        backfill=True, eager_ready=True, sleep_enabled=True,
        ipm_enabled=False, rl_enabled=False, rl_grouped=False,
        dvfs_enabled=False, dvfs_rl=False,
        forecast_enabled=False, forecast_dvfs=False,
    )
    assert IPM().params(BasePolicy.FCFS) == PolicyParams(
        backfill=False, eager_ready=False, sleep_enabled=True,
        ipm_enabled=True, rl_enabled=False, rl_grouped=False,
        dvfs_enabled=False, dvfs_rl=False,
        forecast_enabled=False, forecast_dvfs=False,
    )
    from repro.core.policy import DVFS, AlwaysOn, RLController

    assert AlwaysOn().params(BasePolicy.EASY).sleep_enabled is False
    pp = RLController(grouped=True).params(BasePolicy.EASY)
    assert pp.rl_enabled and pp.rl_grouped and pp.eager_ready
    assert not pp.dvfs_enabled
    pp = DVFS().params(BasePolicy.EASY)
    assert pp.dvfs_enabled and not pp.dvfs_rl and not pp.sleep_enabled
    pp = RLController(dvfs=True).params(BasePolicy.EASY)
    assert pp.dvfs_enabled and pp.dvfs_rl and pp.rl_enabled
    pp = TimeoutSleep(dvfs=True).params(BasePolicy.EASY)
    assert pp.dvfs_enabled and pp.sleep_enabled and not pp.dvfs_rl


def test_sweep_label_and_policy_scenarios():
    """Scenario spellings: a label string and a bare PowerPolicy land on the
    same traced point as the explicit mapping."""
    wl = generate_workload(GeneratorConfig(n_jobs=30, nb_res=16, seed=9))
    plat = PlatformSpec(nb_nodes=16)
    cfg = EngineConfig(base=BasePolicy.EASY, timeout=300)
    batch = engine.sweep(
        plat, wl,
        ["EASY PSAS", TimeoutSleep(transition_aware=True),
         {"scheduler": "EASY PSAS"}],
        cfg,
    )
    e0 = np.asarray(batch.state_at(0).energy)
    np.testing.assert_array_equal(e0, np.asarray(batch.state_at(1).energy))
    np.testing.assert_array_equal(e0, np.asarray(batch.state_at(2).energy))


def test_sweep_jit_cache_is_bounded():
    """The sweep program cache is an LRU of bounded size: a long-lived grid
    search cannot accumulate compiled programs without limit."""
    wl = generate_workload(GeneratorConfig(n_jobs=5, nb_res=8, seed=0))
    plat = PlatformSpec(nb_nodes=8)
    for w in range(engine._SWEEP_CACHE_SIZE + 3):
        engine.sweep(plat, wl, [60], EngineConfig(window=w + 1))
        assert len(engine._SWEEP_FNS) <= engine._SWEEP_CACHE_SIZE
    assert len(engine._SWEEP_FNS) == engine._SWEEP_CACHE_SIZE


def test_cli_experiment_flag(tmp_path, monkeypatch):
    """launch/sim.py --experiment runs a spec file end to end."""
    from repro.launch.sim import main as sim_main

    # main() turns on the persistent compile cache; tests stay uncached
    monkeypatch.setattr("repro.launch.sim.use_compile_cache", lambda: None)

    spec = tmp_path / "exp.json"
    experiments.Experiment(
        name="cli",
        workload={"preset": "fig3_small", "n_jobs": 30},
        platform=16,
        schedulers=("EASY PSUS", "EASY PSAS"),
        timeouts=(120,),
        out=str(tmp_path / "out"),
    ).save(str(spec))
    result = sim_main(["--experiment", str(spec)])
    assert len(result.rows) == 2
    assert os.path.exists(tmp_path / "out" / "metrics.json")


def test_unknown_sim_config_key_suggests(tmp_path):
    from repro.launch.sim import run as sim_run

    with pytest.raises(ValueError, match="did you mean 'timeout'"):
        sim_run(
            {"workload": "preset:fig3_small", "platform": 16,
             "timeot": 300, "gantt": False, "out": str(tmp_path)}
        )
    with pytest.raises(ValueError, match="did you mean 'checkpoint'"):
        sim_run(
            {"workload": "preset:fig3_small", "platform": 16,
             "scheduler": "EASY RL", "rl": {"checkpont": "x"},
             "gantt": False, "out": str(tmp_path)}
        )
