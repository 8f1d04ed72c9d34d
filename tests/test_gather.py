"""The sweep gather (core/SEMANTICS.md §Device-sharded sweeps, Gather).

``PendingSweep.result()`` copies the stacked final state to the host in
one transfer and builds every lane's metrics from numpy views of it. The
metrics must equal per-lane reads of ``SimBatch.states`` exactly, with
and without a device mesh (four virtual CPU devices in a subprocess,
nine lanes, so three pad rows are dropped), and ``metrics_from_state``
must never be handed device arrays.
"""
import textwrap

import jax
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core import engine
from repro.core.types import BasePolicy, EngineConfig, PSMVariant
from repro.workloads.generator import GeneratorConfig, generate_workload
from repro.workloads.platform import PlatformSpec

SCENARIOS = ["EASY PSUS", "FCFS PSAS+IPM",
             {"scheduler": "EASY PSAS", "timeout": 60}, 600]
NINE = SCENARIOS * 2 + [900]


def _grid():
    plat = PlatformSpec(nb_nodes=16)
    wl = generate_workload(GeneratorConfig(n_jobs=30, nb_res=16, seed=3))
    return plat, wl, EngineConfig(base=BasePolicy.EASY, psm=PSMVariant.PSUS)


def check_parity(batch, plat, k):
    """Every lane's metrics equal ``metrics_from_state`` of its device
    state, and ``states`` is the device tree with leading axis ``k``."""
    from repro.core.metrics import metrics_from_state

    assert len(batch.metrics) == k
    for leaf in jax.tree_util.tree_leaves(batch.states):
        assert isinstance(leaf, jax.Array) and leaf.shape[0] == k
    for i, m in enumerate(batch.metrics):
        assert m == metrics_from_state(batch.state_at(i), plat), i


class HostViewSpy:
    """Stands in for ``metrics_from_state``: fails on a lane state that
    holds a device array, then delegates."""

    def __init__(self, real):
        self.real, self.calls = real, 0

    def __call__(self, s, power_active):
        for name, v in s._asdict().items():
            assert not isinstance(v, jax.Array), name
            assert isinstance(v, (np.ndarray, np.generic)), (name, type(v))
        self.calls += 1
        return self.real(s, power_active)


@pytest.mark.parametrize("devices", [None, 1])
def test_metrics_equal_per_lane_reads(devices):
    plat, wl, cfg = _grid()
    batch = engine.sweep(plat, wl, NINE, cfg, devices=devices)
    check_parity(batch, plat, len(NINE))


def test_metrics_equal_per_lane_reads_on_four_devices():
    out = run_subprocess(textwrap.dedent("""
        import sys
        sys.path.insert(0, "tests")
        from test_gather import NINE, _grid, check_parity
        from repro.core import engine
        plat, wl, cfg = _grid()
        batch = engine.sweep(plat, wl, NINE, cfg, devices=4)
        assert batch.devices == 4
        assert int(batch.states.energy.shape[0]) == 9
        check_parity(batch, plat, 9)
        print("ok")
    """), n_devices=4)
    assert out.strip().endswith("ok")


def test_metrics_are_built_from_host_views(monkeypatch):
    import repro.core.metrics as metrics

    spy = HostViewSpy(metrics.metrics_from_state)
    monkeypatch.setattr(metrics, "metrics_from_state", spy)
    plat, wl, cfg = _grid()
    batch = engine.sweep(plat, wl, SCENARIOS, cfg)
    assert spy.calls == len(SCENARIOS) == len(batch)


def test_metrics_are_built_from_host_views_on_four_devices():
    out = run_subprocess(textwrap.dedent("""
        import sys
        sys.path.insert(0, "tests")
        import repro.core.metrics as metrics
        from test_gather import NINE, HostViewSpy, _grid
        from repro.core import engine
        spy = HostViewSpy(metrics.metrics_from_state)
        metrics.metrics_from_state = spy
        plat, wl, cfg = _grid()
        batch = engine.sweep(plat, wl, NINE, cfg, devices=4)
        assert spy.calls == 9 == len(batch)
        print("ok")
    """), n_devices=4)
    assert out.strip().endswith("ok")
