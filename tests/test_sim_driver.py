"""The paper's runner.py analogue: config-file-driven simulation runs."""
import json
import os

import pytest

from repro.core.policy import scheduler_labels
from repro.launch.sim import _load_mini_yaml, run


def test_yaml_subset_parser(tmp_path):
    p = tmp_path / "cfg.yaml"
    p.write_text(
        "workload: preset:fig3_small\n"
        "platform: 16\n"
        "scheduler: EASY PSUS\n"
        "timeout: 50   # comment\n"
        "terminate_overrun: true\n"
        "gantt: false\n"
        "out: out/x\n"
    )
    cfg = _load_mini_yaml(str(p))
    assert cfg["platform"] == 16
    assert cfg["timeout"] == 50
    assert cfg["terminate_overrun"] is True
    assert cfg["gantt"] is False
    assert cfg["scheduler"] == "EASY PSUS"


def test_run_writes_outputs(tmp_path):
    out = str(tmp_path / "run")
    res = run(
        {
            "workload": "preset:fig3_small",
            "platform": 16,
            "scheduler": "EASY PSUS",
            "timeout": 50,
            "terminate_overrun": True,
            "out": out,
        }
    )
    assert res["n_jobs"] == 200
    assert os.path.exists(os.path.join(out, "metrics.json"))
    assert os.path.exists(os.path.join(out, "jobs.csv"))
    assert os.path.exists(os.path.join(out, "gantt.csv"))
    with open(os.path.join(out, "jobs.csv")) as f:
        lines = f.read().strip().splitlines()
    assert len(lines) == 201  # header + 200 jobs


def test_all_schedulers_resolvable(tmp_path):
    for name in scheduler_labels():  # every non-RL registry label
        res = run(
            {
                "workload": "preset:fig3_small",
                "platform": 16,
                "scheduler": name,
                "timeout": 300,
                "gantt": False,
                "out": str(tmp_path / name.replace(" ", "_")),
            }
        )
        assert res["total_energy_kwh"] > 0, name


def test_rl_scheduler_runs_from_checkpoint(tmp_path):
    """'EASY RL' + rl: {checkpoint} drives run_sim with the saved policy."""
    import jax

    from repro.core.rl.env import EnvConfig
    from repro.core.rl.networks import policy_init
    from repro.training.checkpoint import save_policy

    ecfg = EnvConfig()
    params = policy_init(jax.random.PRNGKey(0), ecfg.obs_size, ecfg.n_actions)
    ckpt = str(tmp_path / "policy")
    save_policy(
        ckpt, params,
        obs_size=ecfg.obs_size, n_actions=ecfg.n_actions,
        feature=ecfg.feature, action=ecfg.action,
        n_levels=ecfg.n_action_levels,
    )
    out = str(tmp_path / "rl_run")
    res = run(
        {
            "workload": "preset:fig3_small",
            "platform": 16,
            "scheduler": "EASY RL",
            "rl": {"checkpoint": ckpt, "decision_interval": 600},
            "gantt": False,
            "out": out,
        }
    )
    assert res["scheduler"] == "EASY RL"
    assert res["n_jobs"] == 200
    assert res["total_energy_kwh"] > 0
    assert os.path.exists(os.path.join(out, "metrics.json"))


def test_rl_groups_checkpoint_platform_mismatch_errors(tmp_path):
    """A grouped checkpoint trained for 2 groups must not silently mis-decode
    actions on a 3-group platform."""
    import jax

    from repro.core.rl.networks import policy_init
    from repro.training.checkpoint import save_policy
    from repro.workloads.platform import mixed_platform_example

    params = policy_init(jax.random.PRNGKey(0), 20, 18)  # 2 groups x 9 levels
    ckpt = str(tmp_path / "polg")
    save_policy(
        ckpt, params, obs_size=20, n_actions=18, feature="compact",
        action="group_target_fraction", n_levels=9, grouped=True, n_groups=2,
    )
    with pytest.raises(ValueError, match="node groups"):
        run(
            {
                "workload": "preset:fig3_small",
                "platform": mixed_platform_example(16),  # 3 groups
                "scheduler": "EASY RL:groups",
                "rl": {"checkpoint": ckpt},
                "gantt": False,
                "out": str(tmp_path / "x"),
            }
        )


def test_rl_scheduler_without_checkpoint_errors(tmp_path):
    with pytest.raises(ValueError, match="checkpoint"):
        run(
            {
                "workload": "preset:fig3_small",
                "platform": 16,
                "scheduler": "EASY RL",
                "gantt": False,
                "out": str(tmp_path / "x"),
            }
        )


HETERO_PLATFORM_JSON = {
    "node_groups": [
        {
            "name": "fast",
            "count": 6,
            "compute_speed": 2.0,
            "states": {
                "sleep": {"power": 12.0},
                "idle": {"power": 250.0},
                "active": {"power": 300.0},
                "switching_on": {"power": 300.0, "transition_time": 600},
                "switching_off": {"power": 12.0, "transition_time": 900},
            },
        },
        {
            "name": "eco",
            "count": 10,
            "compute_speed": 0.5,
            "states": {
                "sleep": {"power": 4.0},
                "idle": {"power": 80.0},
                "active": {"power": 100.0},
                "switching_on": {"power": 100.0, "transition_time": 120},
                "switching_off": {"power": 4.0, "transition_time": 180},
            },
        },
    ]
}


def test_golden_run_heterogeneous(tmp_path):
    """Golden-file run: fixed-seed config through the heterogeneous-platform
    JSON input path; metrics.json keys/values and CSV shape are pinned.

    The pinned numbers are the cross-engine semantics (oracle-validated by
    the parity suite) — a change here is a semantics change, not noise.
    """
    plat_path = tmp_path / "platform.json"
    plat_path.write_text(json.dumps(HETERO_PLATFORM_JSON))
    out = str(tmp_path / "run")
    res = run(
        {
            "workload": "preset:fig3_small",  # seeded generator: deterministic
            "platform": str(plat_path),
            "scheduler": "EASY PSAS",
            "timeout": 300,
            "terminate_overrun": True,
            "gantt": False,
            "out": out,
        }
    )

    with open(os.path.join(out, "metrics.json")) as f:
        metrics = json.load(f)
    assert metrics == res
    # keys: the base row plus one per-group energy entry per node group
    assert set(metrics) == {
        "scheduler", "timeout", "total_energy_kwh", "wasted_energy_kwh",
        "mean_wait_s", "max_wait_s", "utilization", "makespan_s",
        "n_jobs", "n_terminated", "energy_kwh.fast", "energy_kwh.eco",
    }
    assert metrics["scheduler"] == "EASY PSAS"
    assert metrics["timeout"] == 300
    assert metrics["n_jobs"] == 200
    # golden values (f64 metrics of the f32-Kahan ledger; exact on rerun)
    assert metrics["total_energy_kwh"] == pytest.approx(
        metrics["energy_kwh.fast"] + metrics["energy_kwh.eco"], rel=1e-9
    )
    assert metrics["total_energy_kwh"] > 0
    assert 0.0 < metrics["utilization"] < 1.0
    assert metrics["makespan_s"] > 0

    # schedule CSV: pinned header + one row per job
    with open(os.path.join(out, "jobs.csv")) as f:
        lines = f.read().strip().splitlines()
    assert lines[0] == "job,res,subtime,start,finish,wait,terminated"
    assert len(lines) == 201  # header + 200 jobs

    # the golden anchor: byte-identical metrics on a re-run (same seed,
    # same platform JSON -> same compiled program -> same f32 ledger)
    out2 = str(tmp_path / "run2")
    res2 = run(
        {
            "workload": "preset:fig3_small",
            "platform": str(plat_path),
            "scheduler": "EASY PSAS",
            "timeout": 300,
            "terminate_overrun": True,
            "gantt": False,
            "out": out2,
        }
    )
    assert res2 == res


def test_job_profiles_workload():
    from repro.configs.job_profiles import build_profiles, profile_workload

    profs = build_profiles()
    # every applicable (arch x shape) cell present: 40 - 8 skips = 32
    assert len(profs) == 32
    names = {p.name for p in profs}
    assert "zamba2-2.7b:long_500k" in names
    assert "glm4-9b:long_500k" not in names
    wl = profile_workload(n_jobs=50, nb_nodes=128, seed=1)
    assert len(wl) == 50
    for j in wl.jobs:
        assert 1 <= j.res <= 128
        assert j.runtime >= 60
        assert j.reqtime >= j.runtime


def test_gantt_log_too_large_fails_before_compiling(tmp_path, monkeypatch):
    """A Gantt log the device cannot hold is refused before compiling, with
    the byte count and the way out in the message."""
    from repro.launch import sim

    monkeypatch.setattr(sim, "_device_bytes_limit", lambda: 10**6)
    out = tmp_path / "run"
    config = {"workload": "preset:fig3_small", "platform": 16, "out": str(out)}
    cap = 20 * 200 + 10_000  # engine.default_batch_cap of 200 jobs
    with pytest.raises(ValueError, match=f"{2 * cap * 16 * 4} bytes.*\"gantt\": false"):
        run(config)
    assert not (out / "metrics.json").exists()
    run(dict(config, gantt=False))  # the way out runs
    assert (out / "metrics.json").exists()


@pytest.mark.parametrize("from_env", [False, True], ids=["checkout", "env"])
def test_compile_cache_placement(tmp_path, monkeypatch, from_env):
    """JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the cache sits at
    the fixed <checkout>/.jax_cache/."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = use_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if from_env:
        assert path == str(tmp_path) and after == before
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == after == os.path.join(repo, ".jax_cache")
