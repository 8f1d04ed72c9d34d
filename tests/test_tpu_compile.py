"""Ahead-of-time compiles for a described TPU v5e, no chip attached.

The chip's compiler refuses what interpret mode accepts (tiles that do not
align, VMEM over budget, programs that do not fit HBM), so the main path is
compiled here at Curie width (11,200 nodes): the three event kernels, the
grouped and the dense ``run_sim`` with the Pallas route inside, and the
4-device sharded sweep. Nothing runs; these prove nothing about results or
speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import engine
from repro.core.policy import from_label
from repro.core.types import EngineConfig
from repro.workloads.generator import PRESETS, generate_workload
from repro.workloads.platform import PlatformSpec, curie_platform

# the module: ``repro.kernels.event_fuse`` as an attribute is the jit wrapper
event_kernels = importlib.import_module("repro.kernels.event_fuse")

N = 11_200  # Curie width
KERNEL_MARK = "tpu_custom_call"
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernel_route(monkeypatch):
    """Make ``kernels/ops`` pick the compiled (not interpreted) kernel, as
    it does on the chip, and keep no trace made that way afterwards."""
    from repro.kernels import ops

    jax.clear_caches()
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    yield
    jax.clear_caches()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding),
        tree,
    )


def _curie_case(grouped: bool):
    """(platform, 2,000-job Curie workload, config) on the kernel route."""
    plat = curie_platform(N) if grouped else PlatformSpec(nb_nodes=N)
    wl = generate_workload(PRESETS["cea_curie"], n_jobs=2000)
    base, pol = from_label("EASY PSUS")
    cfg = EngineConfig(
        base=base, policy=pol, timeout=1800, grouped_tables=grouped,
        node_order="cheap" if grouped else "id", fused_kernel=True,
    )
    return plat, wl, engine.trim_window(cfg, len(wl))


@pytest.mark.parametrize(
    "name", ["event_fuse", "event_fuse_ledger", "event_fuse_occ"]
)
def test_event_kernel_compiles_at_curie_width(one_chip, name):
    i32 = jnp.int32
    node = jax.ShapeDtypeStruct((1, N), i32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((1,), i32, sharding=one_chip)
    kernel = getattr(event_kernels, name)
    if name == "event_fuse_occ":
        gid = jax.ShapeDtypeStruct((N,), i32, sharding=one_chip)
        fn = lambda s, u, t_, g: kernel(s, u, t_, g, 3, interpret=False)
        args = (node, node, t, gid)
    else:
        power = jax.ShapeDtypeStruct((5,), jnp.float32, sharding=one_chip)
        fn = lambda s, u, t_, p: kernel(s, u, t_, p, interpret=False)
        args = (node, node, t, power)
    compiled = jax.jit(fn).lower(*args).compile()
    assert KERNEL_MARK in compiled.as_text()


@pytest.mark.parametrize("grouped", [True, False], ids=["grouped", "dense"])
def test_run_sim_compiles_with_kernel(one_chip, kernel_route, grouped):
    """The single-run program ``engine.simulate`` builds, Pallas route in."""
    plat, wl, cfg = _curie_case(grouped)
    s0 = engine.init_state(plat, wl, cfg)
    const = engine.make_const(plat, cfg, specialize=True)
    pp = const.policy
    fn = jax.jit(lambda s, c: engine.run_sim(s, c._replace(policy=pp), cfg))
    compiled = fn.lower(
        *_shapes((s0, const._replace(policy=None)), one_chip)
    ).compile()
    assert KERNEL_MARK in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
    )
    assert total < V5E_HBM_BYTES, mem


def test_sharded_sweep_compiles_on_four_chips(topo, kernel_route):
    """The sweep program sharded over a 2x2 mesh, 2 scenarios per chip."""
    plat, wl, cfg = _curie_case(grouped=True)
    cap = engine.default_batch_cap(len(wl))
    s0 = engine.init_state(plat, wl, cfg)
    base_const = engine.make_const(plat, cfg)
    consts = [
        engine._scenario_const(t, base_const, plat, cfg)[0]
        for t in (300, 900, 1800, 3600, 600, 1200, 2400, None)
    ]
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *consts)
    mesh = Mesh(np.asarray(topo.devices), ("scenario",))
    fn = engine._sweep_program(cfg, cap, topo.devices)
    compiled = fn.lower(
        _shapes(s0, NamedSharding(mesh, PartitionSpec())),
        _shapes(stacked, NamedSharding(mesh, PartitionSpec("scenario"))),
    ).compile()
    text = compiled.as_text()
    assert KERNEL_MARK in text
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES
