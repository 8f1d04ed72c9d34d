"""Rehearsal of ``chip_smoke.py`` without the chip.

Its phase functions run here at a tiny size on the CPU, which checks paths,
arguments and the checks themselves; ``main()`` is only run to see it
refuse (no TPU here, or no repository around the script). The four-chip
phases run on four forced host devices in a subprocess.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from conftest import REPO, run_subprocess

SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def uncached(monkeypatch):
    """The CLI entry points turn the persistent compile cache on; tests
    stay uncached."""
    monkeypatch.setattr("repro.launch.sim.use_compile_cache", lambda: None)


def test_device_phase_refuses_another_platform(smoke):
    assert smoke.phase_device(platform="cpu")["platform"] == "cpu"
    with pytest.raises(AssertionError, match="refusing"):
        smoke.phase_device(platform="tpu")


def test_paper_run_phase(smoke, uncached, tmp_path, capsys):
    smoke.phase_paper_run(str(tmp_path), workload="preset:fig3_small", nodes=16)
    assert "schedule bit-exact" in capsys.readouterr().out


def test_curie_phase(smoke, uncached, tmp_path, capsys):
    summary = smoke.phase_curie(str(tmp_path), n_jobs=40, nodes=120, prefix=15)
    assert set(summary) == {"event_fuse_occ", "event_fuse_ledger"}
    assert all(r["n_batches"] > 0 for r in summary.values())
    out = capsys.readouterr().out
    assert out.count("bit-exact with the oracle") == 2


def test_grid_and_serve_phases(smoke, uncached, tmp_path):
    result = smoke.phase_grid(str(tmp_path), n_jobs=30, nodes=128)
    assert result.n_compiles == 1 and len(result.rows) == 24
    responses = smoke.phase_serve(str(tmp_path), n_jobs=30, nodes=128)
    assert [r["status"] for r in responses] == ["done", "done"]


def test_serve_phase_fails_on_an_error_response(smoke, tmp_path):
    """The service turns exceptions into error responses; the smoke must
    not let one pass."""
    with pytest.raises(AssertionError, match="request user-a failed"):
        smoke.phase_serve(str(tmp_path), n_jobs=30, nodes=0)


def test_rl_phase(smoke, uncached, tmp_path):
    history = smoke.phase_rl(
        str(tmp_path), nodes=16, n_envs=2, n_updates=1,
        eval_workload="preset:fig3_small",
    )
    assert len(history) == 1
    assert os.path.exists(tmp_path / "rl_run" / "metrics.json")


def test_four_chip_phases_on_host_devices(tmp_path):
    out = run_subprocess(
        textwrap.dedent(
            f"""
            import importlib.util
            spec = importlib.util.spec_from_file_location("chip_smoke", {SMOKE!r})
            smoke = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(smoke)
            assert smoke.phase_device(platform="cpu", min_count=4)["count"] == 4
            many = smoke.phase_sharded_grid({str(tmp_path)!r}, devices=4,
                                            n_jobs=30, nodes=128)
            assert many.n_compiles == 1
            smoke.phase_rl({str(tmp_path)!r}, nodes=16, n_envs=4, n_updates=1,
                           devices=4, replay=False)
            print("OK")
            """
        ),
        n_devices=4,
    )
    assert "byte-identical" in out and out.strip().endswith("OK")


@pytest.mark.parametrize("where", ["no_tpu", "alone"])
def test_main_refuses_before_any_phase(tmp_path, where):
    """Without a TPU, or without the repository around it, the script exits
    non-zero and prints no result line."""
    script = SMOKE
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    res = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, env=env,
        cwd=str(tmp_path), timeout=300,
    )
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "[paper_run]" not in res.stdout
