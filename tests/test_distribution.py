"""Multi-device distribution tests (subprocess with fake devices, so the
main pytest process keeps the 1-device view required by the smoke tests)."""
import textwrap

import pytest

from conftest import run_subprocess


def test_pipeline_parallel_matches_sequential():
    run_subprocess(
        textwrap.dedent(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.training.pipeline import pipeline_forward, split_stages, make_stage_fn
            mesh = jax.make_mesh((4, 2), ("pod", "data"))
            L, D = 8, 16
            rng = np.random.default_rng(0)
            w = jnp.asarray(rng.normal(size=(L, D, D)) * 0.1, jnp.float32)
            block = lambda lp, x: jnp.tanh(x @ lp)
            x = jnp.asarray(rng.normal(size=(6, 3, D)), jnp.float32)
            out = pipeline_forward(make_stage_fn(block), split_stages(w, 4), x, mesh=mesh, axis="pod")
            ref = x
            for i in range(L):
                ref = jnp.tanh(ref @ w[i])
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
            print("OK")
            """
        ),
        n_devices=8,
    )


def test_data_parallel_train_step_matches_single_device():
    """DP over 4 devices == single-device step (same global batch)."""
    run_subprocess(
        textwrap.dedent(
            """
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.configs import get_arch
            from repro.models import build_model
            from repro.models.sharding import batch_shardings, params_shardings
            from repro.training.train_step import TrainStepConfig, make_optimizer, make_train_step

            cfg = get_arch("internlm2-1.8b", reduced=True).replace(remat=False)
            model = build_model(cfg)
            opt = make_optimizer("adamw", 1e-3)
            step = make_train_step(model, opt, TrainStepConfig())
            params = model.init(jax.random.PRNGKey(0))
            batch = {"tokens": jnp.asarray(
                np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 32)), jnp.int32)}

            p1, _, m1 = jax.jit(step)(params, opt.init(params), batch)

            mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
            with mesh:
                p_sh = params_shardings(cfg, mesh, jax.eval_shape(lambda: params))
                b_sh = batch_shardings(mesh, jax.eval_shape(lambda: batch), 8)
                params_d = jax.tree_util.tree_map(jax.device_put, params, p_sh)
                batch_d = jax.tree_util.tree_map(jax.device_put, batch, b_sh)
                pN, _, mN = jax.jit(step)(params_d, opt.init(params_d), batch_d)

            assert abs(float(m1["loss"]) - float(mN["loss"])) < 1e-4, (m1, mN)
            for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(pN)):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    atol=5e-3, rtol=5e-3)
            print("OK")
            """
        ),
        n_devices=8,
    )


def test_elastic_reshard_preserves_values():
    run_subprocess(
        textwrap.dedent(
            """
            import jax, jax.numpy as jnp, numpy as np, functools
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.training.elastic import reshard, surviving_mesh

            tree = {"a": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((3,))}
            fn = lambda mesh, shapes: jax.tree_util.tree_map(
                lambda s: NamedSharding(
                    mesh, P("data", None) if len(s.shape) == 2 else P()), shapes)
            m8 = surviving_mesh(8, 1)
            t8 = reshard(tree, m8, fn)
            m4 = surviving_mesh(4, 1)   # half the fleet died
            t4 = reshard(t8, m4, fn)
            for k in tree:
                np.testing.assert_array_equal(np.asarray(t4[k]), np.asarray(tree[k]))
            m8b = surviving_mesh(8, 1)  # nodes came back
            t8b = reshard(t4, m8b, fn)
            for k in tree:
                np.testing.assert_array_equal(np.asarray(t8b[k]), np.asarray(tree[k]))
            print("OK")
            """
        ),
        n_devices=8,
    )


def test_rl_envs_shard_over_data_axis():
    """The paper's RL loop vmapped over envs, sharded over 'data'."""
    run_subprocess(
        textwrap.dedent(
            """
            import functools, jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core.engine import init_state, make_const
            from repro.core.rl.env import EnvConfig, env_reset, env_step
            from repro.core.types import BasePolicy, EngineConfig, PSMVariant
            from repro.workloads.generator import GeneratorConfig, generate_workload
            from repro.workloads.platform import PlatformSpec

            plat = PlatformSpec(nb_nodes=16)
            wl = generate_workload(GeneratorConfig(n_jobs=24, nb_res=16, seed=0))
            cfg = EnvConfig(engine=EngineConfig(
                psm=PSMVariant.RL, base=BasePolicy.EASY, rl_decision_interval=600))
            const = make_const(plat, cfg.engine)
            sim0 = init_state(plat, wl, cfg.engine)
            E = 16
            sims = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (E,) + a.shape), sim0)
            mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
            shard = lambda t: jax.tree_util.tree_map(
                lambda x: jax.device_put(
                    x, NamedSharding(mesh, P(*(("data",) + (None,) * (x.ndim - 1))))),
                t)
            with mesh:
                sims = shard(sims)
                states, obs = jax.jit(jax.vmap(functools.partial(env_reset, cfg, const)))(sims)
                step = jax.jit(jax.vmap(functools.partial(env_step, cfg, const)))
                states, obs, r, done, info = step(states, jnp.zeros((E,), jnp.int32))
            assert obs.shape == (E, cfg.obs_size)
            print("OK")
            """
        ),
        n_devices=8,
    )


def test_dryrun_single_cell():
    """One full-size dry-run cell lowers + compiles on the 16x16 mesh."""
    run_subprocess(
        textwrap.dedent(
            """
            from repro.launch.dryrun import lower_cell
            rec = lower_cell("whisper-tiny", "decode_32k", multi_pod=False)
            assert rec["status"] == "ok", rec
            assert rec["flops_per_device"] > 0
            assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")
            print("OK", rec["roofline"]["dominant"])
            """
        ),
        n_devices=512,
        timeout=900,
    )


def test_hlo_analysis_counts_scan_trips():
    """Trip-count-aware FLOP accounting vs hand-computed scan matmul."""
    run_subprocess(
        textwrap.dedent(
            """
            import jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.launch.hlo_analysis import analyze_hlo
            mesh = jax.make_mesh((2, 4), ("data", "model"))
            def step(w, x):
                def body(c, _):
                    return jnp.tanh(c @ w), ()
                y, _ = jax.lax.scan(body, x, None, length=3)
                return y.sum()
            w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
            x = jax.ShapeDtypeStruct((256, 512), jnp.float32)
            with mesh:
                comp = jax.jit(step, in_shardings=(
                    NamedSharding(mesh, P(None, "model")),
                    NamedSharding(mesh, P("data", None)))).lower(w, x).compile()
            cost = analyze_hlo(comp.as_text(), 8)
            want = 3 * 2 * 128 * 128 * 512  # 3 trips x per-device dot
            assert abs(cost.flops - want) / want < 0.01, (cost.flops, want)
            assert cost.collective_counts.get("all-gather", 0) == 3.0
            print("OK")
            """
        ),
        n_devices=8,
    )
