"""A2C learner (the paper's ref [24] trains A2C power managers; ref [7] adds
curriculum learning — see ``examples/train_rl_power_manager.py``).

The rollout is a ``lax.scan`` over vmapped env steps, so one update =
one XLA program; environments auto-reset. ``make_update_fn`` returns a jitted
(or pjit-sharded) update usable both on CPU for the paper-scale agent and on
the production mesh (env batch sharded over ``("pod","data")``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import EngineConst, SimState, init_state, make_const
from repro.core.rl.env import EnvConfig, EnvState, env_reset, env_step
from repro.core.rl.networks import policy_apply, policy_init
from repro.training.optimizer import adamw, apply_updates, clip_by_global_norm
from repro.workloads.platform import PlatformSpec
from repro.workloads.workload import Workload


@dataclasses.dataclass(frozen=True)
class A2CConfig:
    n_envs: int = 32
    n_steps: int = 16
    gamma: float = 0.99
    gae_lambda: float = 0.95
    lr: float = 3e-4
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    max_grad_norm: float = 0.5
    n_updates: int = 200
    hidden: Tuple[int, ...] = (128, 128)
    seed: int = 0


class Rollout(NamedTuple):
    obs: jax.Array  # [T, B, obs]
    actions: jax.Array  # [T, B]
    rewards: jax.Array  # [T, B]
    dones: jax.Array  # [T, B] done AFTER the step
    values: jax.Array  # [T, B] value at obs
    last_value: jax.Array  # [B]
    live: jax.Array  # [T, B] env was live when acting


def make_batched_sims(
    platform: PlatformSpec,
    workloads: Sequence[Workload],
    env_cfg: EnvConfig,
    job_capacity: Optional[int] = None,
) -> SimState:
    cap = job_capacity or max(len(w) for w in workloads)
    sims = [
        init_state(platform, w, env_cfg.engine, job_capacity=cap)
        for w in workloads
    ]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *sims)


def collect_rollout(
    params,
    env_states: EnvState,
    obs: jax.Array,
    key: jax.Array,
    sims0: SimState,
    env_cfg: EnvConfig,
    const: EngineConst,
    n_steps: int,
) -> Tuple[EnvState, jax.Array, jax.Array, Rollout]:
    """T steps of the vmapped env with auto-reset; returns data for the loss."""
    reset_fn = jax.vmap(functools.partial(env_reset, env_cfg, const))
    step_fn = jax.vmap(functools.partial(env_step, env_cfg, const))

    def one_step(carry, _):
        env_states, obs, key = carry
        # auto-reset envs that finished on the previous step
        fresh_states, fresh_obs = reset_fn(sims0)
        need_reset = env_states.done
        env_states = jax.tree_util.tree_map(
            lambda f, c: jnp.where(
                need_reset.reshape((-1,) + (1,) * (c.ndim - 1)), f, c
            ),
            fresh_states,
            env_states,
        )
        obs = jnp.where(need_reset[:, None], fresh_obs, obs)

        logits, value = jax.vmap(policy_apply, (None, 0))(params, obs)
        key, k = jax.random.split(key)
        action = jax.random.categorical(k, logits)
        live = ~env_states.done
        env_states, next_obs, reward, done, _ = step_fn(env_states, action)
        out = (obs, action, reward, done, value, live)
        return (env_states, next_obs, key), out

    (env_states, obs, key), (obs_t, act_t, rew_t, done_t, val_t, live_t) = (
        jax.lax.scan(one_step, (env_states, obs, key), None, length=n_steps)
    )
    _, last_value = jax.vmap(policy_apply, (None, 0))(params, obs)
    roll = Rollout(obs_t, act_t, rew_t, done_t, val_t, last_value, live_t)
    return env_states, obs, key, roll


def gae(roll: Rollout, gamma: float, lam: float) -> Tuple[jax.Array, jax.Array]:
    """Generalized advantage estimation over the [T, B] rollout."""

    def back(carry, x):
        adv_next, v_next = carry
        reward, done, value = x
        nonterm = 1.0 - done.astype(jnp.float32)
        delta = reward + gamma * v_next * nonterm - value
        adv = delta + gamma * lam * nonterm * adv_next
        return (adv, value), adv

    (_, _), advs = jax.lax.scan(
        back,
        (jnp.zeros_like(roll.last_value), roll.last_value),
        (roll.rewards, roll.dones, roll.values),
        reverse=True,
    )
    returns = advs + roll.values
    return advs, returns


def a2c_loss(params, roll: Rollout, advs, returns, cfg: A2CConfig):
    logits, values = jax.vmap(jax.vmap(policy_apply, (None, 0)), (None, 0))(
        params, roll.obs
    )
    logp_all = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(logp_all, roll.actions[..., None], axis=-1)[..., 0]
    mask = roll.live.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    adv_n = (advs - jnp.sum(advs * mask) / n) / (
        jnp.sqrt(jnp.sum(jnp.square(advs) * mask) / n) + 1e-6
    )
    pg = -jnp.sum(logp * jax.lax.stop_gradient(adv_n) * mask) / n
    vf = jnp.sum(jnp.square(values - returns) * mask) / n
    ent = -jnp.sum(jnp.sum(jnp.exp(logp_all) * logp_all, -1) * mask) / n
    loss = pg + cfg.vf_coef * vf - cfg.ent_coef * ent
    return loss, {"pg_loss": pg, "vf_loss": vf, "entropy": ent}


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    env_states: EnvState
    obs: jax.Array
    key: jax.Array


def make_update_fn(
    env_cfg: EnvConfig,
    const: EngineConst,
    sims0: SimState,
    cfg: A2CConfig,
    optimizer=None,
    devices=None,
) -> Callable[[TrainState], Tuple[TrainState, dict]]:
    """The jittable A2C update. ``devices`` (core/SEMANTICS.md
    §Device-sharded sweeps, RL layer) shards the env batch across a 1-D
    local-device mesh: each device rolls out its ``n_envs / D`` slice
    (data-parallel) and the gradient is psum-reduced across the mesh
    before the (replicated) optimizer step — the classic DDP shape, so
    params stay bit-identical on every device."""
    opt = optimizer or adamw(lr=cfg.lr)
    D = _resolve_rollout_devices(devices, env_cfg, cfg.n_envs)

    def update(ts: TrainState, sims) -> Tuple[TrainState, dict]:
        if D is None:
            key_roll = ts.key
        else:
            # per-shard RNG: fold the mesh position into a split of the
            # replicated key, so shards explore independently while the
            # carried TrainState.key stays replicated
            key_roll = jax.random.fold_in(
                jax.random.split(ts.key)[1], jax.lax.axis_index("env")
            )
        env_states, obs, key, roll = collect_rollout(
            ts.params, ts.env_states, ts.obs, key_roll, sims, env_cfg,
            const, cfg.n_steps,
        )
        advs, returns = gae(roll, cfg.gamma, cfg.gae_lambda)
        (loss, aux), grads = jax.value_and_grad(a2c_loss, has_aux=True)(
            ts.params, roll, advs, returns, cfg
        )
        if D is not None:
            # psum/D gradient reduction: the update consumes the mean of
            # the per-shard gradients (identical on every device)
            grads = jax.lax.pmean(grads, "env")
            key = jax.random.split(ts.key)[0]  # replicated successor
        grads, gnorm = clip_by_global_norm(grads, cfg.max_grad_norm)
        updates, opt_state = opt.update(grads, ts.opt_state, ts.params)
        params = apply_updates(ts.params, updates)
        mask = roll.live.astype(jnp.float32)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "mean_reward": jnp.sum(roll.rewards * mask)
            / jnp.maximum(jnp.sum(mask), 1.0),
            **aux,
        }
        if D is not None:
            metrics = {k: jax.lax.pmean(v, "env") for k, v in metrics.items()}
        return TrainState(params, opt_state, env_states, obs, key), metrics

    return _maybe_shard_update(update, sims0, D), opt


def _resolve_rollout_devices(devices, env_cfg: EnvConfig, n_envs: int):
    """Resolve the rollout device count (None = unsharded; falls back to
    ``env_cfg.engine.devices``) and validate the batch divides across it."""
    from repro.core.engine import _resolve_devices

    D = _resolve_devices(devices, env_cfg.engine)
    if D is None or D == 1:
        return None
    if n_envs % D:
        raise ValueError(
            f"n_envs={n_envs} does not shard evenly across {D} devices; "
            "size the env batch to a device multiple"
        )
    return D


def _maybe_shard_update(update, sims0: SimState, D) -> Callable:
    """Close the reset pool into the update; with a device count, lower it
    through ``shard_map`` on the 1-D ``("env",)`` mesh: params/opt
    state/key replicated, env batch (and the reset pool) sharded."""
    if D is None:
        return lambda ts: update(ts, sims0)
    from repro.core.rl.env import rollout_mesh

    P = jax.sharding.PartitionSpec
    ts_spec = TrainState(
        params=P(), opt_state=P(), env_states=P("env"), obs=P("env"), key=P()
    )
    sharded = jax.shard_map(
        update,
        mesh=rollout_mesh(D),
        in_specs=(ts_spec, P("env")),
        out_specs=(ts_spec, P()),
        check_vma=False,
    )
    return lambda ts: sharded(ts, sims0)


def train_a2c(
    platform: PlatformSpec,
    workloads: Sequence[Workload],
    env_cfg: EnvConfig,
    cfg: A2CConfig = A2CConfig(),
    progress: Optional[Callable[[int, dict], None]] = None,
    devices=None,
):
    """Paper-scale A2C training loop (single host). Returns (params, history).

    ``devices`` shards the ``n_envs`` rollout batch across local devices
    (data-parallel + psum'd gradients — §Device-sharded sweeps, RL layer);
    ``None`` falls back to ``env_cfg.engine.devices``, unsharded when that
    is None too."""
    from repro.core.rl.env import shard_env_batch

    # closure constant of the jitted update: specialize the policy flags so
    # every rollout step traces only the RL stack's rules
    const = make_const(platform, env_cfg.engine, specialize=True)
    wls = list(workloads)
    if len(wls) < cfg.n_envs:
        wls = (wls * ((cfg.n_envs + len(wls) - 1) // len(wls)))[: cfg.n_envs]
    sims0 = make_batched_sims(platform, wls[: cfg.n_envs], env_cfg)
    sims0 = shard_env_batch(sims0, devices, env_cfg.engine)

    key = jax.random.PRNGKey(cfg.seed)
    key, kp = jax.random.split(key)
    params = policy_init(kp, env_cfg.obs_size, env_cfg.n_actions, cfg.hidden)
    update, opt = make_update_fn(env_cfg, const, sims0, cfg, devices=devices)
    opt_state = opt.init(params)

    env_states, obs = jax.vmap(functools.partial(env_reset, env_cfg, const))(sims0)
    ts = TrainState(params, opt_state, env_states, obs, key)

    update_j = jax.jit(update)
    history = []
    for i in range(cfg.n_updates):
        ts, metrics = update_j(ts)
        metrics = {k: float(v) for k, v in metrics.items()}
        history.append(metrics)
        if progress:
            progress(i, metrics)
    return ts.params, history
