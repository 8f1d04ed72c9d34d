"""Device-sharded sweeps, streaming experiment runs, partition-aware
allocation, and the simulation service (core/SEMANTICS.md §Device-sharded
sweeps, §Partition-aware allocation).

Multi-device lanes run in subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (conftest's
``run_subprocess``), so the main pytest process keeps its 1-device view.
"""
import dataclasses
import textwrap

import numpy as np
import pytest

from conftest import run_subprocess
from repro import experiments
from repro.core import engine
from repro.core.metrics import metrics_from_state, schedule_table
from repro.core.ref.pydes import run_pydes
from repro.core.types import BasePolicy, EngineConfig, PSMVariant
from repro.workloads.generator import GeneratorConfig, generate_workload
from repro.workloads.platform import PlatformSpec, mixed_platform_example


# ------------------------------------------------------- device resolution

def test_resolve_devices_validation():
    cfg = EngineConfig()
    assert engine._resolve_devices(None, cfg) is None
    assert engine._resolve_devices("all", cfg) >= 1
    assert engine._resolve_devices(1, cfg) == 1
    # None falls back to config.devices
    assert engine._resolve_devices(None, dataclasses.replace(cfg, devices=1)) == 1
    with pytest.raises(ValueError, match="devices must be >= 1"):
        engine._resolve_devices(0, cfg)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        engine._resolve_devices(1_000_000, cfg)


def test_config_devices_validation():
    EngineConfig(devices=1)
    EngineConfig(devices="all")
    with pytest.raises(ValueError):
        EngineConfig(devices=0)
    with pytest.raises(ValueError):
        EngineConfig(devices="half")


def test_sweep_devices_one_matches_unsharded():
    """The D=1 mesh path (shard_map over one device) is bit-exact with the
    legacy unsharded jit(vmap) dispatch."""
    plat = PlatformSpec(nb_nodes=16)
    wl = generate_workload(GeneratorConfig(n_jobs=30, nb_res=16, seed=0))
    cfg = EngineConfig(base=BasePolicy.EASY, psm=PSMVariant.PSUS)
    scenarios = [60, 300, None]
    ref = engine.sweep(plat, wl, scenarios, cfg)
    sh = engine.sweep(plat, wl, scenarios, cfg, devices=1)
    assert sh.devices == 1 and ref.devices is None
    for a, b in zip(
        np.asarray(ref.states.energy), np.asarray(sh.states.energy)
    ):
        np.testing.assert_array_equal(a, b)
    for ma, mb in zip(ref.metrics, sh.metrics):
        assert ma.total_energy_j == mb.total_energy_j
        assert ma.makespan_s == mb.makespan_s


def test_sweep_cache_stats_tick_and_key_separation():
    """Hit/miss accounting (the service layer's reuse ledger) and the
    cache-key rule: sharded and unsharded programs of the same grid never
    share an entry."""
    plat = PlatformSpec(nb_nodes=8)
    wl = generate_workload(GeneratorConfig(n_jobs=12, nb_res=8, seed=4))
    cfg = EngineConfig(base=BasePolicy.EASY, psm=PSMVariant.PSUS)
    scenarios = [60, 600]

    s0 = engine.cache_stats()
    first = engine.sweep(plat, wl, scenarios, cfg)
    s1 = engine.cache_stats()
    again = engine.sweep(plat, wl, scenarios, cfg)
    s2 = engine.cache_stats()
    sharded = engine.sweep(plat, wl, scenarios, cfg, devices=1)
    s3 = engine.cache_stats()

    assert s1["sweep_misses"] == s0["sweep_misses"] + 1
    assert not first.cache_hit
    assert s2 == {**s1, "sweep_hits": s1["sweep_hits"] + 1}
    assert again.cache_hit
    # same grid, devices=1: a different program (new miss), not a reuse
    assert s3["sweep_misses"] == s2["sweep_misses"] + 1
    assert not sharded.cache_hit


def test_sweep_async_overlap_handle():
    """sweep_async returns before result(); result() is idempotent and
    equals the blocking sweep."""
    plat = PlatformSpec(nb_nodes=8)
    wl = generate_workload(GeneratorConfig(n_jobs=12, nb_res=8, seed=4))
    cfg = EngineConfig(base=BasePolicy.EASY, psm=PSMVariant.PSUS)
    pending = engine.sweep_async(plat, wl, [60, 600], cfg)
    batch = pending.result()
    assert pending.result() is batch
    ref = engine.sweep(plat, wl, [60, 600], cfg)
    for ma, mb in zip(batch.metrics, ref.metrics):
        assert ma.total_energy_j == mb.total_energy_j


# ------------------------------------------------------- streaming runner

def _stream_spec(out):
    return experiments.Experiment(
        name="stream",
        workload={"preset": "fig3_small", "n_jobs": 30},
        platform=16,
        schedulers=("EASY PSUS", "FCFS PSAS"),
        timeouts=(60, 600),
        out=out,
    )


def test_streaming_matches_blocking_bytes(tmp_path):
    """stream=True yields chunk-by-chunk; rows AND the on-disk
    metrics.json / rows.csv bytes equal the blocking path's."""
    out = tmp_path / "out"
    exp = _stream_spec(str(out))
    blocking = experiments.run(exp)
    golden = {
        p: (out / p).read_bytes() for p in ("metrics.json", "rows.csv")
    }

    sr = experiments.run(exp, stream=True, chunk_scenarios=3)
    chunks = list(sr)
    assert sr.result is not None
    # 4 scenarios in chunks of <=3 -> two chunks, grid order preserved
    assert [len(c) for c in chunks] == [3, 1]
    flat = [r for c in chunks for r in c]
    assert flat == list(sr.result.rows) == list(blocking.rows)
    for p, want in golden.items():
        assert (out / p).read_bytes() == want, f"{p} diverged from blocking"


@pytest.mark.parametrize("chunk", [1, 2])
def test_streaming_bytes_match_blocking_at_chunk_size(tmp_path, chunk):
    """Every chunk size gathers to the blocking path's metrics.json /
    rows.csv bytes, one gather per chunk."""
    out = tmp_path / "out"
    exp = _stream_spec(str(out))
    experiments.run(exp)
    golden = {p: (out / p).read_bytes() for p in ("metrics.json", "rows.csv")}
    sr = experiments.run(exp, stream=True, chunk_scenarios=chunk)
    assert [len(c) for c in sr] == [chunk] * (4 // chunk)
    for p, want in golden.items():
        assert (out / p).read_bytes() == want, p


def test_streaming_partial_prefix_on_disk(tmp_path):
    """An abandoned stream leaves a valid rows-so-far prefix on disk."""
    out = tmp_path / "out"
    sr = experiments.run(_stream_spec(str(out)), stream=True, chunk_scenarios=1)
    first = next(sr)
    import json

    with open(out / "metrics.json") as f:
        payload = json.load(f)
    assert payload["rows"] == list(first)


def test_chunk_scenarios_requires_stream(tmp_path):
    with pytest.raises(ValueError, match="chunk_scenarios"):
        experiments.run(_stream_spec(str(tmp_path)), chunk_scenarios=2)


# ------------------------------------------------- partition-aware allocation

PARTITION_LABELS = [
    (BasePolicy.EASY, PSMVariant.PSUS),
    (BasePolicy.FCFS, PSMVariant.PSAS),
    (BasePolicy.EASY, PSMVariant.PSAS_IPM),
]


@pytest.mark.parametrize("base,psm", PARTITION_LABELS)
def test_partition_allocation_oracle_parity(base, psm):
    """allocation='partition' on the 3-group mixed platform: engine ==
    oracle bit-exact, and the constraint actually changes the schedule
    relative to allocation='any' (the test is not vacuous)."""
    plat = mixed_platform_example(16)  # fast(5) / eco(5) / std(6)
    wl = generate_workload(
        GeneratorConfig(n_jobs=60, nb_res=16, max_res=5, seed=1, overrun_prob=0.2)
    )
    cfg = EngineConfig(
        base=base, psm=psm, timeout=300, terminate_overrun=True,
        allocation="partition",
    )
    s = engine.simulate(plat, wl, cfg)
    m_ref, des = run_pydes(plat, wl, cfg)
    tab = schedule_table(s)
    np.testing.assert_array_equal(tab, des.schedule_table())
    assert (tab[:, 0] >= 0).all()  # max_res=5 fits every group: all start
    m = metrics_from_state(s, plat)
    assert m.total_energy_j == pytest.approx(m_ref.total_energy_j, rel=1e-5)
    tab_any = schedule_table(
        engine.simulate(plat, wl, dataclasses.replace(cfg, allocation="any"))
    )
    assert not np.array_equal(tab, tab_any)


def test_partition_grouped_tables_bit_exact():
    """The grouped-tables fast path honours the partition constraint
    identically to the dense path."""
    plat = mixed_platform_example(16)
    wl = generate_workload(
        GeneratorConfig(n_jobs=60, nb_res=16, max_res=5, seed=1, overrun_prob=0.2)
    )
    cfg = EngineConfig(
        base=BasePolicy.EASY, psm=PSMVariant.PSUS, timeout=300,
        terminate_overrun=True, allocation="partition",
    )
    dense = engine.simulate(plat, wl, cfg)
    grp = engine.simulate(
        plat, wl, dataclasses.replace(cfg, grouped_tables=True)
    )
    np.testing.assert_array_equal(schedule_table(dense), schedule_table(grp))


def test_partition_oversize_job_fails_to_start():
    """A job wider than every group never starts under
    allocation='partition' (rather than binding across groups), on both
    engines; allocation='any' runs it."""
    plat = mixed_platform_example(16)  # largest group: std(6)
    wl = generate_workload(GeneratorConfig(n_jobs=20, nb_res=16, max_res=5, seed=3))
    big = dataclasses.replace(wl.jobs[5], res=7)
    wl = dataclasses.replace(wl, jobs=wl.jobs[:5] + (big,) + wl.jobs[6:])
    cfg = EngineConfig(
        base=BasePolicy.EASY, psm=PSMVariant.PSUS, timeout=300,
        allocation="partition",
    )
    tab = schedule_table(engine.simulate(plat, wl, cfg))
    _, des = run_pydes(plat, wl, cfg)
    np.testing.assert_array_equal(tab, des.schedule_table())
    assert tab[5, 0] == -1  # never started
    assert (np.delete(tab[:, 0], 5) >= 0).all()  # EASY backfills past it
    tab_any = schedule_table(
        engine.simulate(plat, wl, dataclasses.replace(cfg, allocation="any"))
    )
    assert tab_any[5, 0] >= 0


def test_allocation_validation():
    EngineConfig(allocation="partition")
    with pytest.raises(ValueError):
        EngineConfig(allocation="spread")


def test_experiment_spec_carries_allocation(tmp_path):
    exp = experiments.Experiment(
        name="part", workload={"preset": "fig3_small", "n_jobs": 10},
        platform=16, allocation="partition",
    )
    assert exp.engine_config().allocation == "partition"
    again = experiments.Experiment.from_json(exp.to_json())
    assert again.allocation == "partition"


# ------------------------------------------------------- simulation service

def test_sim_serve_smoke_cache_reuse(tmp_path):
    """Two same-shaped requests through SimService: the second reuses the
    first's compiled grid (all hits, zero misses)."""
    from repro.launch import sim_serve

    sim_serve._smoke(devices=None)


def test_sim_serve_bad_request_is_an_error_response(tmp_path, capsys):
    """A malformed spec produces an error response (and response file)
    without killing the service; a good spec queued alongside still runs."""
    from repro.launch.sim_serve import serve

    req = tmp_path / "req"
    req.mkdir()
    (req / "broken.json").write_text('{"name": "broken"}')  # no workload
    _stream_spec(None).save(str(req / "good.json"))
    responses = serve(str(req), str(tmp_path / "resp"), once=True)
    by_name = {r["request"]: r for r in responses}
    assert by_name["broken"]["status"] == "error"
    assert "error" in by_name["broken"]
    assert by_name["good"]["status"] == "done"
    assert by_name["good"]["rows"] == 4
    assert (tmp_path / "resp" / "broken.response.json").exists()
    assert (tmp_path / "resp" / "good.response.json").exists()


# --------------------------------------------- multi-device (subprocess) lanes

def test_sharded_grid_six_by_four_bit_exact():
    """Acceptance grid: 6 schedulers x 4 timeouts on 8 forced host devices
    — one compile, rows and on-disk bytes identical to the 1-device run."""
    run_subprocess(
        textwrap.dedent(
            """
            import json, pathlib, tempfile
            import jax
            assert jax.device_count() == 8
            from repro import experiments
            from repro.core.policy import scheduler_labels

            six = tuple(l for l in scheduler_labels() if "AlwaysOn" not in l)
            out = pathlib.Path(tempfile.mkdtemp()) / "out"
            exp = experiments.Experiment(
                name="shard6x4",
                workload={"preset": "fig3_small", "n_jobs": 30},
                platform=16,
                schedulers=six,
                timeouts=(60, 300, 600, None),
                out=str(out),
            )
            ref = experiments.run(exp)
            golden = {p: (out / p).read_bytes()
                      for p in ("metrics.json", "rows.csv")}
            sh = experiments.run(exp, devices=8)
            assert sh.n_compiles == 1, sh.n_compiles
            assert list(sh.rows) == list(ref.rows)
            for p, want in golden.items():
                assert (out / p).read_bytes() == want, p
            print("OK", len(sh.rows))
            """
        ),
        n_devices=8,
    )


def test_sharded_pad_rows_masked_and_oracle_parity():
    """K=5 grid on 8 devices (pad 3 rows): pad rows are dropped on gather,
    per-scenario results are bit-exact vs unsharded AND vs the sequential
    oracle."""
    run_subprocess(
        textwrap.dedent(
            """
            import numpy as np
            import jax
            assert jax.device_count() == 8
            from repro.core import engine
            from repro.core.metrics import schedule_table
            from repro.core.ref.pydes import run_pydes
            from repro.core.types import BasePolicy, EngineConfig, PSMVariant
            from repro.workloads.generator import GeneratorConfig, generate_workload
            from repro.workloads.platform import PlatformSpec

            plat = PlatformSpec(nb_nodes=16)
            wl = generate_workload(GeneratorConfig(n_jobs=20, nb_res=16, seed=0))
            cfg = EngineConfig(base=BasePolicy.EASY, psm=PSMVariant.PSUS)
            scenarios = [60, 120, 300, 600, None]   # K=5 -> pad to 8
            ref = engine.sweep(plat, wl, scenarios, cfg)
            sh = engine.sweep(plat, wl, scenarios, cfg, devices=8)
            assert sh.devices == 8
            assert int(sh.states.energy.shape[0]) == 5  # pad rows masked
            for fld in ("energy", "job_start", "job_finish", "t"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ref.states, fld)),
                    np.asarray(getattr(sh.states, fld)),
                    err_msg=f"sharded/unsharded diverged in {fld}",
                )
            # oracle parity per scenario row
            import dataclasses
            for i, t in enumerate(scenarios):
                c = dataclasses.replace(
                    cfg, timeout=t if t is not None else None)
                _, des = run_pydes(plat, wl, c)
                row = jax.tree_util.tree_map(lambda a: a[i], sh.states)
                np.testing.assert_array_equal(
                    schedule_table(row), des.schedule_table(),
                    err_msg=f"scenario {t} diverged from oracle",
                )
            print("OK")
            """
        ),
        n_devices=8,
    )


def test_sharded_rl_training_runs():
    """A2C/PPO data-parallel rollout on 8 devices: envs shard over the
    mesh, gradients pmean-reduce, training produces finite losses."""
    run_subprocess(
        textwrap.dedent(
            """
            import jax
            import numpy as np
            assert jax.device_count() == 8
            from repro.core.rl.a2c import A2CConfig, train_a2c
            from repro.core.rl.ppo import PPOConfig, train_ppo
            from repro.core.rl.env import EnvConfig, shard_env_batch, rollout_mesh
            from repro.core.types import BasePolicy, EngineConfig, PSMVariant
            from repro.workloads.generator import GeneratorConfig, generate_workload
            from repro.workloads.platform import PlatformSpec

            plat = PlatformSpec(nb_nodes=16)
            wl = generate_workload(GeneratorConfig(n_jobs=16, nb_res=16, seed=0))
            ecfg = EnvConfig(engine=EngineConfig(
                psm=PSMVariant.RL, base=BasePolicy.EASY,
                rl_decision_interval=600))

            params, history = train_a2c(
                plat, [wl], ecfg,
                A2CConfig(n_envs=16, n_steps=4, n_updates=2, seed=0),
                devices=8)
            assert np.isfinite(history[-1]["loss"])

            params, history = train_ppo(
                plat, [wl], ecfg,
                PPOConfig(n_envs=16, n_steps=4, n_minibatches=2,
                          n_epochs=1, n_updates=2, seed=0),
                devices=8)
            assert np.isfinite(history[-1]["loss"])

            # env-batch sharding validation
            import jax.numpy as jnp
            x = jnp.zeros((16, 3))
            xs = shard_env_batch(x, 8)
            assert xs.sharding.spec == jax.sharding.PartitionSpec("env")
            try:
                shard_env_batch(jnp.zeros((15, 3)), 8)
            except ValueError as e:
                assert "shard evenly" in str(e)
            else:
                raise AssertionError("indivisible env batch not rejected")
            print("OK")
            """
        ),
        n_devices=8,
    )
