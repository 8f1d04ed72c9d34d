"""Vectorized discrete-event engine — SPARS's contribution, TPU-native.

The paper's engine walks a heap of events; here the simulation state lives in
fixed-capacity arrays and each iteration of a ``lax.while_loop`` processes
*one event batch*: every event sharing the next timestamp, atomically
(core/SEMANTICS.md). The paper's same-time-batching guarantee (its Fig. 1
bug-fix vs Batsim) is therefore structural — a vectorized timestep cannot
split simultaneous events.

Everything is pure-functional over :class:`SimState`, so the engine jits,
vmaps over thousands of environments (the RL use-case: envs sharded over the
mesh ``data`` axis), and vmaps over platform values (e.g. a timeout sweep is
a single compiled program).

Static configuration (window size, node ordering mode, overrun handling)
lives in :class:`EngineConfig`; *everything else* — timeout, per-node
transition times, powers, speeds, **and the policy axis itself** — lives in
:class:`EngineConst` as traced operands, so parameter sweeps never
recompile. The scheduler/policy structure is lowered to
:class:`repro.core.policy.PolicyParams` (traced flags in
``EngineConst.policy``): :func:`process_batch`, :func:`_ready_times`, and
:func:`next_time` evaluate one flag-gated *superset* program that is
bit-exact with the per-config compiles it replaced, and :func:`sweep` vmaps
a whole scheduler x policy x timeout x platform grid through ONE compiled
program (core/SEMANTICS.md §Traced policy axis).

Single-config runs take the *static specialization* path instead
(core/SEMANTICS.md §Static specialization): :func:`simulate` folds the
``PolicyParams`` flags in as Python closure constants
(``PolicyParams.static()``), so every flag gate becomes a Python branch
(:func:`repro.core.policy.static_bool`) and the rules that are off never
enter the trace — one cached compile per config (bounded LRU), bit-exact
with the superset program. :func:`sweep` keeps the traced axis and its
one-compile-per-grid guarantee.
Heterogeneous platforms (mixed node groups with different power models,
transition delays, and compute speeds) are first-class: every node-indexed
quantity is a per-node table and energy is accounted per node group
(core/SEMANTICS.md §Heterogeneity).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from collections import OrderedDict
from typing import Any, Mapping, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.policy import (
    PolicyParams,
    PowerPolicy,
    alloc_min_speed,
    apply_dvfs,
    apply_forecast,
    apply_rl_commands,
    effective_node_speed,
    from_label,
    ipm_wake,
    pack_key,
    static_bool,
    timeout_switch_off,
)
from repro.core.tables import GroupTables, group_tables
from repro.core.types import (
    ACTIVE,
    ALLOCATED,
    DONE,
    IDLE,
    INF_TIME,
    N_STATES,
    RUNNING,
    SLEEP,
    SWITCHING_OFF,
    SWITCHING_ON,
    WAITING,
    EngineConfig,
    SimMetrics,
)
from repro.workloads.platform import PlatformSpec
from repro.workloads.workload import Workload

I32 = jnp.int32
INF = jnp.asarray(INF_TIME, I32)


class EngineConst(NamedTuple):
    """Dynamic (traced) per-run platform tables — sweepable without recompile.

    All node-indexed members are per-node arrays (core/SEMANTICS.md
    §Heterogeneity); :func:`make_const` broadcasts the homogeneous scalars
    lazily, so a sweep over platform values is still one compiled program —
    the arrays are traced operands, never static config.
    """

    power: jax.Array  # f32[N, 5] per-node per-state watts
    t_on: jax.Array  # i32[N] switch-on delay (s)
    t_off: jax.Array  # i32[N] switch-off delay (s)
    speed: jax.Array  # f32[N] compute speed (realized runtime = work/speed)
    order_key: jax.Array  # f32[N] allocation preference (lower = cheaper/faster)
    group_id: jax.Array  # i32[N] node-group index (per-group energy accounting)
    timeout: jax.Array  # i32 idle-timeout (s); INF_TIME = never
    rl_interval: jax.Array  # i32 RL decision tick; INF_TIME = event-driven only
    policy: PolicyParams  # traced policy axis (bool flags; SEMANTICS.md)
    # runtime DVFS mode tables (§DVFS): per-group absolute operating points,
    # sorted ascending by speed; M (table width) is a shape, the values are
    # traced — DVFS-table sweeps vmap like every other platform quantity
    dvfs_speed: jax.Array  # f32[G, M] node speed in mode m
    dvfs_watts: jax.Array  # f32[G, M] ACTIVE-state watts in mode m
    dvfs_n_modes: jax.Array  # i32[G] live modes per group (<= M; rest padding)
    # rule 10 (§Forecast): EWMA predictor operands. Traced like timeout /
    # rl_interval, so a forecast-horizon sweep vmaps through one program;
    # whether the rule runs is the traced ``policy.forecast_enabled`` flag.
    forecast_horizon: jax.Array  # i32 look-ahead seconds (0 = no pressure)
    forecast_alpha: jax.Array  # f32 EWMA smoothing weight in [0, 1]
    # group-indexed tables (§Group-indexed tables): per-group lowering of
    # the per-node tables above, present iff ``config.grouped_tables``.
    # Presence is pytree/trace structure (mirrored in _static_trace_key);
    # the member arrays are traced operands like every other table.
    tables: Optional[GroupTables] = None


class SimState(NamedTuple):
    t: jax.Array  # i32 scalar
    # nodes
    node_state: jax.Array  # i32[N]
    node_until: jax.Array  # i32[N] transition completion (INF otherwise)
    node_job: jax.Array  # i32[N] allocated job (-1 = unreserved)
    node_idle_since: jax.Array  # i32[N]
    # jobs (submission order)
    job_res: jax.Array  # i32[J]
    job_subtime: jax.Array  # i32[J]
    job_reqtime: jax.Array  # i32[J]
    job_run: jax.Array  # i32[J] nominal runtime (work at speed 1)
    job_eff: jax.Array  # i32[J] effective runtime (speed + overrun folded in at start)
    job_status: jax.Array  # i32[J]
    job_start: jax.Array  # i32[J] (-1 until started)
    job_finish: jax.Array  # i32[J] (INF until started)
    job_alloc_ready: jax.Array  # i32[J] predicted start at allocation
    job_exists: jax.Array  # bool[J] (False for padding)
    job_terminated: jax.Array  # bool[J]
    # accounting (Kahan-compensated f32 per node group x state)
    energy: jax.Array  # f32[G, 5]
    energy_c: jax.Array  # f32[G, 5]
    wait_integral: jax.Array  # f32: ∫ #(arrived ∧ not-started) dt
    wait_c: jax.Array  # Kahan compensation
    # counters (Table-4-style breakdown)
    n_batches: jax.Array
    n_allocs: jax.Array
    n_starts: jax.Array
    n_completions: jax.Array
    n_switch_on: jax.Array
    n_switch_off: jax.Array
    # RL pending commands: i32[G] per-group (#nodes to wake / sleep at the
    # next batch; global-action mode reads the vector sums — core/policy.py)
    rl_on_cmd: jax.Array
    rl_off_cmd: jax.Array
    # runtime DVFS (§DVFS): current per-group mode, pending agent mode
    # commands (-1 = no change), each running job's current effective speed
    # (the remaining-work rescale anchor), and the mode ledgers
    dvfs_mode: jax.Array  # i32[G]
    rl_mode_cmd: jax.Array  # i32[G]
    job_speed: jax.Array  # f32[J]
    mode_time: jax.Array  # f32[G, M] residency seconds (accrues when enabled)
    mode_energy: jax.Array  # f32[G, M] ACTIVE energy by mode
    # set by run_sim/run_sim_gantt when the batch/log cap stopped the run
    # before completion — metrics from a truncated state are partial
    truncated: jax.Array  # bool
    # per-(group, state) node occupancy histogram (§Group-indexed tables):
    # on the grouped-tables path this is refreshed at every energy accrual
    # with the histogram of the interval just accrued (invariant:
    # occ.sum(axis=1) == tables.count); the dense path leaves it at its
    # initial value — it is a grouped-path cache, not dense-path state
    occ: jax.Array  # i32[G, 5]
    # rule 10 (§Forecast) EWMA predictor state, updated by apply_forecast
    # only where the forecast flag is on — all four stay at their inits
    # (and contribute nothing) under every other stack
    fc_gap: jax.Array  # f32 smoothed inter-arrival gap (init INF_TIME)
    fc_res: jax.Array  # f32 smoothed nodes asked per arrival (init 0)
    fc_last_arr: jax.Array  # i32 time of the last observed arrival burst
    fc_prev_t: jax.Array  # i32 previous predictor update time (init -1)


class GanttLog(NamedTuple):
    t0: jax.Array  # i32[cap]
    t1: jax.Array  # i32[cap]
    state: jax.Array  # i32[cap, N]
    job: jax.Array  # i32[cap, N]
    n: jax.Array  # i32 rows used


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def make_const(
    platform: PlatformSpec,
    config: EngineConfig,
    specialize: bool = False,
) -> EngineConst:
    """Lower (platform, config) to the engine's traced tables.

    ``specialize=True`` carries the policy axis as *concrete* Python bools
    (``PolicyParams.static()``) instead of traced flags: the right choice
    for a const that is closed over by a single-config program (the RL
    env/learners, ``run_sim_gantt`` drivers) — disabled rules are then
    pruned at trace time. A specialized const must NOT be stacked into a
    sweep (``sweep`` builds its own traced consts) and loses its
    specialization if passed through a jit boundary as an argument (the
    bools become traced operands again — correct, just not specialized).
    """
    N = platform.nb_nodes
    if platform.node_groups:
        power = jnp.asarray(platform.node_power_table(), jnp.float32)
        t_on = jnp.asarray(platform.node_t_switch_on(), I32)
        t_off = jnp.asarray(platform.node_t_switch_off(), I32)
        speed = jnp.asarray(platform.node_speed(), jnp.float32)
        if config.node_order == "idle-watts":
            order_key = power[:, IDLE]
        elif config.node_order == "pack":
            # the pack key is dynamic queue state, recomputed once per
            # scheduler pass (policy.pack_key); the static key is unused
            order_key = jnp.zeros(N, jnp.float32)
        else:
            order_key = jnp.asarray(platform.node_order_key(), jnp.float32)
        group_id = jnp.asarray(platform.node_group_id(), I32)
    else:
        # homogeneous: broadcast the scalars lazily (no N-sized host copies)
        power = jnp.broadcast_to(
            jnp.asarray(platform.power_table(), jnp.float32), (N, 5)
        )
        t_on = jnp.broadcast_to(jnp.asarray(platform.t_switch_on, I32), (N,))
        t_off = jnp.broadcast_to(jnp.asarray(platform.t_switch_off, I32), (N,))
        speed = jnp.broadcast_to(
            jnp.asarray(platform.speed(), jnp.float32), (N,)
        )
        if config.node_order == "idle-watts":
            key = np.float32(platform.power_idle)
        elif config.node_order == "pack":
            key = np.float32(0.0)  # dynamic key — see the hetero branch
        else:
            # same f32 expression as PlatformSpec.node_order_key()
            key = np.float32(platform.power_active) / np.float32(
                platform.speed()
            )
        order_key = jnp.broadcast_to(jnp.asarray(key, jnp.float32), (N,))
        group_id = jnp.zeros(N, I32)
    dvfs_speed, dvfs_watts, dvfs_n = platform.group_dvfs_tables()
    # rule 10 operands: EngineConfig wins for the horizon; a Forecast
    # policy's horizon/alpha fields are the fallback defaults (the enable
    # flag itself rides the policy axis — core/SEMANTICS.md §Forecast)
    horizon = config.forecast_horizon
    if horizon is None:
        horizon = getattr(config.policy, "horizon", None) or 0
    alpha = getattr(config.policy, "alpha", None)
    if alpha is None:
        alpha = config.forecast_alpha
    return EngineConst(
        power=power,
        t_on=t_on,
        t_off=t_off,
        speed=speed,
        order_key=order_key,
        group_id=group_id,
        timeout=jnp.asarray(config.timeout_or_inf, I32),
        rl_interval=jnp.asarray(
            config.rl_decision_interval or int(INF_TIME), I32
        ),
        policy=(
            config.policy.params(config.base).static()
            if specialize
            else config.policy.params(config.base).traced()
        ),
        dvfs_speed=jnp.asarray(dvfs_speed, jnp.float32),
        dvfs_watts=jnp.asarray(dvfs_watts, jnp.float32),
        dvfs_n_modes=jnp.asarray(dvfs_n, I32),
        forecast_horizon=jnp.asarray(int(horizon), I32),
        forecast_alpha=jnp.asarray(float(alpha), jnp.float32),
        tables=(
            group_tables(platform, config) if config.grouped_tables else None
        ),
    )


def init_state(
    platform: PlatformSpec,
    workload: Workload,
    config: EngineConfig,
    job_capacity: Optional[int] = None,
    start_state: int = IDLE,
) -> SimState:
    """Build the initial SimState (host-side, numpy)."""
    arrs = workload.arrays()
    n = len(arrs["res"])
    J = job_capacity or n
    if J < n:
        raise ValueError(f"job_capacity {J} < {n} jobs")
    N = platform.nb_nodes

    def pad(x, fill):
        out = np.full(J, fill, np.int32)
        out[:n] = x
        return out

    res = pad(arrs["res"], 1)
    subtime = pad(arrs["subtime"], int(INF_TIME))
    reqtime = pad(arrs["reqtime"], 1)
    runtime = pad(arrs["runtime"], 1)
    # DVFS / compute-speed model: ``runtime`` is nominal work at speed 1.
    # The realized wall time depends on the speed of the nodes a job lands
    # on, so it is resolved in _start_jobs (core/SEMANTICS.md §Heterogeneity)
    # — overrun is judged there on realized time.
    status = np.full(J, WAITING, np.int32)
    status[n:] = DONE
    exists = np.zeros(J, bool)
    exists[:n] = True
    G = platform.n_groups()
    # every node starts in start_state, so the occupancy histogram starts
    # as the per-group node counts in that state's column
    occ0 = np.zeros((G, 5), np.int32)
    occ0[:, start_state] = np.bincount(
        platform.node_group_id(), minlength=G
    ).astype(np.int32)

    return SimState(
        t=jnp.asarray(0, I32),
        node_state=jnp.full(N, start_state, I32),
        node_until=jnp.full(N, int(INF_TIME), I32),
        node_job=jnp.full(N, -1, I32),
        node_idle_since=jnp.zeros(N, I32),
        job_res=jnp.asarray(res),
        job_subtime=jnp.asarray(subtime),
        job_reqtime=jnp.asarray(reqtime),
        job_run=jnp.asarray(runtime),
        job_eff=jnp.asarray(runtime),
        job_status=jnp.asarray(status),
        job_start=jnp.full(J, -1, I32),
        job_finish=jnp.full(J, int(INF_TIME), I32),
        job_alloc_ready=jnp.full(J, int(INF_TIME), I32),
        job_exists=jnp.asarray(exists),
        job_terminated=jnp.zeros(J, bool),
        energy=jnp.zeros((G, 5), jnp.float32),
        energy_c=jnp.zeros((G, 5), jnp.float32),
        wait_integral=jnp.zeros((), jnp.float32),
        wait_c=jnp.zeros((), jnp.float32),
        n_batches=jnp.asarray(0, I32),
        n_allocs=jnp.asarray(0, I32),
        n_starts=jnp.asarray(0, I32),
        n_completions=jnp.asarray(0, I32),
        n_switch_on=jnp.asarray(0, I32),
        n_switch_off=jnp.asarray(0, I32),
        rl_on_cmd=jnp.zeros(G, I32),
        rl_off_cmd=jnp.zeros(G, I32),
        dvfs_mode=jnp.zeros(G, I32),
        rl_mode_cmd=jnp.full(G, -1, I32),
        job_speed=jnp.ones(J, jnp.float32),
        mode_time=jnp.zeros((G, platform.n_dvfs_modes()), jnp.float32),
        mode_energy=jnp.zeros((G, platform.n_dvfs_modes()), jnp.float32),
        truncated=jnp.asarray(False),
        occ=jnp.asarray(occ0),
        fc_gap=jnp.asarray(float(INF_TIME), jnp.float32),
        fc_res=jnp.zeros((), jnp.float32),
        fc_last_arr=jnp.asarray(0, I32),
        fc_prev_t=jnp.asarray(-1, I32),
    )


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _clamp_job(idx: jax.Array) -> jax.Array:
    return jnp.maximum(idx, 0)


def _ready_times(s: SimState, const: EngineConst) -> jax.Array:
    """Policy-dependent node ready times (SEMANTICS.md table); INF for ACTIVE.

    ``const.policy.eager_ready`` is read through :func:`static_bool`: as a
    *traced* flag (sweeps) both columns of the ready-time table are
    evaluated and selected per scenario, so a vmapped sweep can mix eager
    (AlwaysOn/PSUS/RL) and transition-aware (PSAS/IPM) policies in one
    compiled program; as a concrete bool (the specialized single-config
    path) only the live column is traced.
    """
    t = s.t
    eager_b = static_bool(const.policy.eager_ready)
    if eager_b is not False:
        eager = jnp.where(
            s.node_state == ACTIVE, INF, jnp.full_like(s.node_state, 0) + t
        )
    if eager_b is not True:
        aware = jnp.select(
            [
                s.node_state == IDLE,
                s.node_state == SWITCHING_ON,
                s.node_state == SLEEP,
                s.node_state == SWITCHING_OFF,
            ],
            [
                jnp.broadcast_to(t, s.node_state.shape),
                s.node_until,
                jnp.broadcast_to(t + const.t_on, s.node_state.shape),
                s.node_until + const.t_on,
            ],
            default=jnp.broadcast_to(INF, s.node_state.shape),
        )
    if eager_b is None:
        return jnp.where(const.policy.eager_ready, eager, aware).astype(I32)
    return (eager if eager_b else aware).astype(I32)


def _occupancy(s: SimState, const: EngineConst) -> jax.Array:
    """i32[G, 5] per-(group, state) node histogram (§Group-indexed tables).

    The one O(N) reduction of the grouped hot path — a single scatter-add
    (or the Pallas ``event_fuse_occ`` kernel) replacing the per-node power
    gather + [G, 5] scatter the dense path pays every accrual. Twin of the
    oracle's ``_occupancy``.
    """
    G = s.energy.shape[0]
    return (
        jnp.zeros((G, N_STATES), I32)
        .at[const.group_id, s.node_state]
        .add(1)
    )


def _group_draw(s: SimState, occ: jax.Array, const: EngineConst) -> jax.Array:
    """f32[G, 5] instantaneous draw from the occupancy histogram — the
    grouped spelling of :func:`_node_power_draw` (``occ · power`` with the
    ACTIVE column overridden by the group's current DVFS mode watts). The
    single expression shared by the grouped fused pass and the grouped
    legacy accrual, so the two loop shapes stay fully bit-exact."""
    draw = occ.astype(jnp.float32) * const.tables.power
    dvfs_on = const.policy.dvfs_enabled
    if static_bool(dvfs_on) is not False:
        G = s.energy.shape[0]
        mode_w = const.dvfs_watts[jnp.arange(G), s.dvfs_mode]
        draw = draw.at[:, ACTIVE].set(
            jnp.where(
                dvfs_on,
                occ[:, ACTIVE].astype(jnp.float32) * mode_w,
                draw[:, ACTIVE],
            )
        )
    return draw


def _kahan_add(energy, comp, delta):
    y = delta - comp
    t = energy + y
    comp = (t - energy) - y
    return t, comp


# ---------------------------------------------------------------------------
# event-batch phases (SEMANTICS.md rules 1..8)
# ---------------------------------------------------------------------------

def _complete_jobs(s: SimState) -> SimState:
    done_now = (s.job_status == RUNNING) & (s.job_finish <= s.t)
    job_status = jnp.where(done_now, DONE, s.job_status)
    nj = s.node_job
    node_of_done = (nj >= 0) & done_now[_clamp_job(nj)]
    return s._replace(
        job_status=job_status,
        node_job=jnp.where(node_of_done, -1, nj),
        node_state=jnp.where(node_of_done, IDLE, s.node_state),
        node_until=jnp.where(node_of_done, INF, s.node_until),
        node_idle_since=jnp.where(node_of_done, s.t, s.node_idle_since),
        n_completions=s.n_completions + jnp.sum(done_now, dtype=I32),
    )


def _complete_transitions(s: SimState, const: EngineConst) -> SimState:
    on_done = (s.node_state == SWITCHING_ON) & (s.node_until <= s.t)
    off_done = (s.node_state == SWITCHING_OFF) & (s.node_until <= s.t)
    chain = off_done & (s.node_job >= 0)  # reserved while shutting down
    node_state = jnp.where(on_done, IDLE, s.node_state)
    node_state = jnp.where(off_done, SLEEP, node_state)
    node_state = jnp.where(chain, SWITCHING_ON, node_state)
    node_until = jnp.where(on_done | off_done, INF, s.node_until)
    node_until = jnp.where(chain, s.t + const.t_on, node_until)
    node_idle_since = jnp.where(on_done, s.t, s.node_idle_since)
    return s._replace(
        node_state=node_state,
        node_until=node_until,
        node_idle_since=node_idle_since,
    )


def _queue_window(s: SimState, W: int) -> jax.Array:
    """Indices of the first W WAITING-and-arrived jobs; -1 padding."""
    waiting = (s.job_status == WAITING) & (s.job_subtime <= s.t)
    rank = jnp.cumsum(waiting) - 1  # rank among waiting jobs
    J = s.job_status.shape[0]
    dest = jnp.where(waiting & (rank < W), rank, W)
    window = jnp.full(W + 1, -1, I32).at[dest].set(jnp.arange(J, dtype=I32))
    return window[:W]


def _partition_pick(es, gid, res_j, n_groups):
    """Per-group masked-cumsum pick (SEMANTICS.md §Partition-aware
    allocation): ``es``/``gid`` are node eligibility and group id laid out
    in allocation order. Each group counts its eligible nodes along the
    order; a group is feasible iff its total reaches ``res_j``, and the
    winner is the group whose ``res_j``-th eligible node appears earliest
    in the order (the earliest-completing group; positions are distinct
    nodes, so no ties are possible). Returns the in-order selection mask
    and the any-group-fits predicate. Host twin:
    ``PyDES._partition_select``.
    """
    N = es.shape[0]
    onehot = (
        gid[None, :] == jnp.arange(n_groups, dtype=gid.dtype)[:, None]
    ) & es[None, :]
    csum = jnp.cumsum(onehot.astype(I32), axis=1)  # [G, N] running counts
    feasible_g = csum[:, -1] >= res_j
    pos = jnp.argmax(csum >= res_j, axis=1)  # first completion position
    best = jnp.argmin(jnp.where(feasible_g, pos, N))
    feasible = jnp.any(feasible_g)
    sel = onehot[best] & (csum[best] <= res_j) & feasible
    return sel, feasible


def _try_allocate(s, const, cfg, j, shadow, extra,
                  order=None, ready_f=None, okey=None):
    """Attempt to allocate job j. Returns (ok, new_state, ready_max).

    shadow < 0 means head-phase (no backfill constraint).

    Node selection order (core/SEMANTICS.md §Heterogeneity): nodes are taken
    by ``(ready, order_key, nid)`` — with ``cfg.node_order == "id"`` the
    ``order_key`` term is dropped, reproducing the homogeneous tie-breaking
    ``(ready, nid)``; with ``"cheap"`` the per-node ``const.order_key``
    (active watts per unit work, lower first) steers allocation onto
    cheap/fast nodes, with ``"idle-watts"`` the key is the node's idle
    draw (prefer nodes that are cheapest to leave powered), and with
    ``"pack"`` it is the per-pass dynamic packing key (``okey``, from
    :func:`repro.core.policy.pack_key`).

    The ready times come from the traced ``const.policy.eager_ready`` flag
    (see :func:`_ready_times`): under an eager policy every eligible node has
    ready == t, so the stable argsort's tie-breaking degenerates to the
    legacy "first res_j unreserved by id" selection bit-exactly, and under a
    key ordering to a pure order-key sort — one program covers both columns
    of the ready-time table. (The pre-traced-axis engine special-cased the
    eager path to an O(N) cumsum; that specialization is the price of the
    one-compile policy grid, see SEMANTICS.md §Traced vs static.)

    Grouped-tables path (§Group-indexed tables): ``order`` is the node
    order hoisted out of the attempt loop by ``_scheduler_pass`` (the
    per-pass sort — or the precomputed ``tables.perm``, zero sorts, when
    the policy is statically eager). Selection is then a masked cumsum
    over ``order`` — the first ``res_j`` *eligible* nodes in order — which
    picks the same nodes as the dense per-attempt masked argsorts: the
    sort keys of still-eligible nodes are loop-invariant within a pass
    (allocation only reserves nodes or wakes SLEEP→SWITCHING_ON, both of
    which make the node ineligible and, for the aware ready column, leave
    its ready time t+t_on unchanged), a stable sort preserves the relative
    order of the eligible subsequence, and the cumsum skips the
    interleaved ineligible nodes the masked sort would have pushed to the
    end. ``ready_f`` is the pass-hoisted ready-time vector (None under a
    statically eager policy, where every chosen node is ready at ``t``);
    ``ready_max`` agrees with the dense spelling wherever ``ok`` can be
    True — the only place it is consumed.

    Partition mode (§Partition-aware allocation, ``cfg.allocation ==
    "partition"``): cross-group allocations are forbidden. Scanning the
    same allocation order, the job takes the first ``res_j`` eligible
    nodes of the earliest-completing single group (:func:`_partition_pick`)
    and fails (``ok=False``, stays WAITING) when no group can hold it —
    instead of binding its realized runtime to the slowest node of a
    mixed allocation. The backfill test and EASY shadow keep their dense
    group-agnostic spelling, mirrored exactly in the oracle.
    """
    eligible = s.node_job < 0
    res_j = s.job_res[j]
    n_elig = jnp.sum(eligible, dtype=I32)
    partition = cfg.allocation == "partition"
    n_groups = const.dvfs_speed.shape[0]
    if order is not None:
        es = eligible[order]
        if partition:
            sel_sorted, feasible = _partition_pick(
                es, const.group_id[order], res_j, n_groups
            )
        else:
            csum = jnp.cumsum(es.astype(I32))
            sel_sorted = es & (csum <= res_j)
            feasible = n_elig >= res_j
        chosen = jnp.zeros_like(eligible).at[order].set(sel_sorted)
        if ready_f is None:  # statically eager: chosen nodes are ready now
            ready_max = s.t
        else:
            ready_max = jnp.max(
                jnp.where(sel_sorted, ready_f[order], -1)
            ).astype(I32)
    else:
        ready = _ready_times(s, const)
        key = jnp.where(eligible, ready, INF)
        if cfg.node_order != "id":
            # lexicographic (ready, order_key, nid): stable argsort by the
            # secondary key first, then by ready over that permutation
            k2 = const.order_key if okey is None else okey
            perm1 = jnp.argsort(
                jnp.where(eligible, k2, jnp.inf), stable=True
            )
            aorder = perm1[jnp.argsort(key[perm1], stable=True)]
        else:
            aorder = jnp.argsort(key, stable=True)  # ties -> lowest node id
        if partition:
            sorted_sel, feasible = _partition_pick(
                eligible[aorder], const.group_id[aorder], res_j, n_groups
            )
        else:
            sorted_sel = jnp.arange(key.shape[0]) < res_j
            feasible = n_elig >= res_j
        ready_sorted = key[aorder]
        ready_max = jnp.max(
            jnp.where(sorted_sel, ready_sorted, -1)
        ).astype(I32)
        chosen = jnp.zeros_like(eligible).at[aorder].set(sorted_sel) & eligible
    pred_completion = ready_max + s.job_reqtime[j]
    bf_ok = (shadow < 0) | (pred_completion <= shadow) | (res_j <= extra)
    ok = feasible & bf_ok
    chosen = chosen & ok
    # reserve + auto-wake chosen sleeping nodes
    wake = chosen & (s.node_state == SLEEP)
    new = s._replace(
        node_job=jnp.where(chosen, j, s.node_job),
        node_state=jnp.where(wake, SWITCHING_ON, s.node_state),
        node_until=jnp.where(wake, s.t + const.t_on, s.node_until),
        job_status=s.job_status.at[j].set(
            jnp.where(ok, ALLOCATED, s.job_status[j])
        ),
        job_alloc_ready=s.job_alloc_ready.at[j].set(
            jnp.where(ok, ready_max, s.job_alloc_ready[j])
        ),
        n_allocs=s.n_allocs + ok.astype(I32),
        n_switch_on=s.n_switch_on + jnp.sum(wake, dtype=I32),
    )
    return ok, new, ready_max


def _shadow(s: SimState, const: EngineConst, head: jax.Array):
    """EASY shadow time S and extra count E for blocked head job."""
    ready = _ready_times(s, const)
    nj = s.node_job
    cj = _clamp_job(nj)
    job_running = s.job_status[cj] == RUNNING
    job_alloc = s.job_status[cj] == ALLOCATED
    pred_of_job = jnp.where(
        job_running,
        s.job_start[cj] + s.job_reqtime[cj],
        jnp.where(job_alloc, s.job_alloc_ready[cj] + s.job_reqtime[cj], s.t),
    )
    rel = jnp.where(nj >= 0, pred_of_job, ready).astype(I32)
    rel_sorted = jnp.sort(rel)
    res_h = s.job_res[head]
    S = rel_sorted[jnp.maximum(res_h - 1, 0)]
    E = jnp.sum(rel <= S, dtype=I32) - res_h
    return S, E


def _sched_attempt(s, const, cfg, j, can_try, shadow, extra, blocked, bf, backfill,
                   order=None, ready_f=None, okey=None):
    """One window-slot attempt: the shared body of both scheduler loops.

    Returns the updated (s, shadow, extra, blocked) carry. ``can_try`` gates
    the attempt (the early-exit loop passes True: its cond already encodes
    validity and the FCFS blocked latch); ``bf``/``backfill`` are the
    static/traced spellings of the policy's backfill flag;
    ``order``/``ready_f``/``okey`` are the pass-hoisted allocation inputs
    (see :func:`_try_allocate`), passed through untouched.
    """
    ok, s_new, _ = _try_allocate(
        s, const, cfg, _clamp_job(j), shadow, extra,
        order=order, ready_f=ready_f, okey=okey,
    )
    take = can_try & ok
    s = jax.tree_util.tree_map(
        lambda a, b: jnp.where(take, b, a), s, s_new
    )
    newly_blocked = can_try & ~ok
    if bf is False:  # FCFS: shadow/extra stay (-1, 0) == head-phase
        return s, shadow, extra, blocked | newly_blocked

    # compute (S, E) at the first blocked EASY head; cond skips the
    # O(N log N) sort on the (common) unblocked iterations
    need_shadow = newly_blocked & (shadow < 0)
    if bf is None:
        need_shadow = need_shadow & backfill
    S, E = jax.lax.cond(
        need_shadow,
        lambda s_: _shadow(s_, const, _clamp_job(j)),
        lambda s_: (jnp.asarray(-1, I32), jnp.asarray(0, I32)),
        s,
    )
    shadow = jnp.where(need_shadow, S, shadow)
    extra = jnp.where(need_shadow, E, extra)
    # backfill consumed part of the extra pool
    extra = jnp.where(
        take & (shadow >= 0), extra - s.job_res[_clamp_job(j)], extra
    )
    return s, shadow, extra, blocked | newly_blocked


def _scheduler_pass(s: SimState, const: EngineConst, cfg: EngineConfig) -> SimState:
    """Rule 4 under the traced ``const.policy.backfill`` flag.

    backfill=True (EASY): every window slot is attempted; the first blocked
    head fixes the shadow time S and extra pool E, and later jobs must pass
    the backfill test. backfill=False (FCFS): attempts stop at the first
    failure (``blocked`` latches) and the shadow machinery never engages
    (shadow stays -1 == head-phase for every attempt). Both behaviours are
    one program, bit-exact with the former per-base compiles. A concrete
    ``backfill`` (the specialized single-config path) traces only the live
    behaviour — FCFS drops the O(N log N) shadow machinery entirely.

    Loop shape (core/SEMANTICS.md §Hot loop): under ``cfg.fused_events`` the
    window scan is a ``while_loop`` that exits at the end of the dense
    prefix (``_queue_window`` packs real jobs first, then -1 padding) — and,
    for FCFS, at the first blocked head — so an empty or short queue pays
    per-batch cost proportional to the *live* queue, not the static W. The
    legacy ``fori_loop`` attempts every slot; both are bit-exact (a -1 slot
    or a latched-blocked FCFS attempt never changes state).

    Grouped tables (§Group-indexed tables): the allocation order is hoisted
    out of the attempt loop — computed once per pass here (zero sorts under
    a statically eager policy, where ``tables.perm`` IS the order) and
    consumed by the cumsum selection in :func:`_try_allocate`. Sound
    because the sort keys of still-eligible nodes are loop-invariant
    within a pass (argument at :func:`_try_allocate`).

    Burst merging (``cfg.merge_bursts``, §Hot loop): the pass repeats at
    the same timestamp while it makes progress and arrived jobs are still
    WAITING, so a burst of more than W newly-runnable jobs drains in ONE
    batch — each repeat sees the next W of the queue (allocated jobs left
    WAITING, so ``_queue_window`` advances) — instead of parking the
    remainder until the next unrelated event. Terminates because
    ``n_allocs`` strictly increases (bounded by J). Fused and legacy loop
    shapes stay bit-exact per label (the repeat wraps both identically);
    the oracle mirrors the same repeat rule.
    """
    backfill = const.policy.backfill
    bf = static_bool(backfill)
    W = cfg.window

    def pass_inputs(s):
        """Per-pass hoisted allocation inputs (order, ready_f, okey)."""
        okey = pack_key(s, const) if cfg.node_order == "pack" else None
        if not cfg.grouped_tables:
            return None, None, okey
        base = (
            jnp.argsort(okey, stable=True)
            if okey is not None
            else const.tables.perm
        )
        if static_bool(const.policy.eager_ready) is True:
            return base, None, okey  # every eligible node is ready at t
        ready_f = _ready_times(s, const)
        return base[jnp.argsort(ready_f[base], stable=True)], ready_f, okey

    def run_pass(s):
        window = _queue_window(s, W)
        order, ready_f, okey = pass_inputs(s)
        shadow0 = jnp.asarray(-1, I32)
        extra0 = jnp.asarray(0, I32)

        if cfg.fused_events:
            def cond(carry):
                _, k, shadow, extra, blocked = carry
                j = window[jnp.minimum(k, W - 1)]
                valid = (k < W) & (j >= 0)
                if bf is True:  # EASY: blocked never gates an attempt
                    return valid
                if bf is False:  # FCFS: stop at the first blocked head
                    return valid & ~blocked
                return valid & (backfill | ~blocked)

            def wbody(carry):
                s, k, shadow, extra, blocked = carry
                j = window[jnp.minimum(k, W - 1)]
                s, shadow, extra, blocked = _sched_attempt(
                    s, const, cfg, j, True, shadow, extra, blocked, bf,
                    backfill, order=order, ready_f=ready_f, okey=okey,
                )
                return s, k + 1, shadow, extra, blocked

            s, _, _, _, _ = jax.lax.while_loop(
                cond,
                wbody,
                (s, jnp.asarray(0, I32), shadow0, extra0, jnp.bool_(False)),
            )
            return s

        def body(k, carry):
            s, shadow, extra, blocked = carry
            j = window[k]
            valid = j >= 0
            # specialized EASY: blocked never gates an attempt (backfill|..)
            can_try = valid if bf else valid & (backfill | ~blocked)
            return _sched_attempt(
                s, const, cfg, j, can_try, shadow, extra, blocked, bf,
                backfill, order=order, ready_f=ready_f, okey=okey,
            )

        s, _, _, _ = jax.lax.fori_loop(
            0, W, body, (s, shadow0, extra0, jnp.bool_(False))
        )
        return s

    if not cfg.merge_bursts:
        return run_pass(s)

    def mcond(carry):
        _, go = carry
        return go

    def mbody(carry):
        s, _ = carry
        before = s.n_allocs
        s = run_pass(s)
        more = (s.n_allocs > before) & jnp.any(
            (s.job_status == WAITING) & (s.job_subtime <= s.t)
        )
        return s, more

    s, _ = jax.lax.while_loop(mcond, mbody, (s, jnp.bool_(True)))
    return s


def _start_jobs(s: SimState, const: EngineConst, cfg: EngineConfig) -> SimState:
    J = s.job_status.shape[0]
    nj = s.node_job
    cj = _clamp_job(nj)
    contrib = ((s.node_state == IDLE) & (nj >= 0)).astype(I32)
    ready_count = jnp.zeros(J, I32).at[cj].add(contrib)
    start = (s.job_status == ALLOCATED) & (ready_count == s.job_res)
    node_starts = (nj >= 0) & start[cj]
    # realized wall time = nominal work / slowest allocated node, resolved
    # now that the allocation is known (core/SEMANTICS.md §Heterogeneity);
    # the f32 ceil is the cross-engine contract — the oracle computes the
    # identical float32 expression so schedules stay bit-exact. Under DVFS
    # the node speed is the group's *current mode* speed (§DVFS).
    node_speed = effective_node_speed(
        const, s.dvfs_mode, const.policy.dvfs_enabled
    )
    speed_min = alloc_min_speed(nj, node_speed, J)
    speed_min = jnp.where(start, speed_min, jnp.float32(1.0))
    realized = jnp.maximum(
        jnp.ceil(s.job_run.astype(jnp.float32) / speed_min).astype(I32), 1
    )
    if cfg.terminate_overrun:
        eff = jnp.minimum(realized, s.job_reqtime)
        term = realized > s.job_reqtime
    else:
        eff = realized
        term = jnp.zeros(J, bool)
    return s._replace(
        job_status=jnp.where(start, RUNNING, s.job_status),
        job_start=jnp.where(start, s.t, s.job_start),
        job_eff=jnp.where(start, eff, s.job_eff),
        job_speed=jnp.where(start, speed_min, s.job_speed),
        job_terminated=jnp.where(start, term, s.job_terminated),
        job_finish=jnp.where(start, s.t + eff, s.job_finish),
        node_state=jnp.where(node_starts, ACTIVE, s.node_state),
        node_until=jnp.where(node_starts, INF, s.node_until),
        n_starts=s.n_starts + jnp.sum(start, dtype=I32),
    )


def _power_step(s: SimState, const: EngineConst, cfg: EngineConfig) -> SimState:
    """Rules 6-10, flag-gated by the policy axis (``const.policy``).

    With traced flags (sweeps) every rule is evaluated in every program; a
    scenario whose flag is off selects zero nodes, leaving state and
    counters bit-identical to a program that never contained the rule.
    With concrete flags (the specialized single-config path) a disabled
    rule is skipped at trace time — bit-identical by the same argument,
    but the dead rule never reaches XLA. The optional in-graph RL
    ``controller`` (a network driving run_sim end-to-end) is the one static
    remnant of policy structure — a callable cannot be a traced operand.
    """
    pp = const.policy
    if static_bool(pp.sleep_enabled) is not False:
        s = timeout_switch_off(s, const, ipm_cap=pp.ipm_enabled,
                               enabled=pp.sleep_enabled)
    if static_bool(pp.ipm_enabled) is not False:
        s = ipm_wake(s, const, enabled=pp.ipm_enabled)
    controller = getattr(cfg.policy, "controller", None)
    if controller is not None:
        out = controller(s, const)
        if getattr(cfg.policy, "dvfs", False) and len(out) < 3:
            # a legacy (on, off) controller under RL:dvfs would silently pin
            # every group at mode 0 (dvfs_rl bypasses the ladder); the
            # arity is static, so fail at trace time instead
            raise ValueError(
                "RLController(dvfs=True) needs a controller returning "
                "(on, off, mode) — this one returns only (on, off), so no "
                "mode command would ever be issued"
            )
        from repro.core.rl.actions import full_commands  # lazy: import cycle

        on, off, mode = full_commands(s, out)
        s = s._replace(
            rl_on_cmd=jnp.broadcast_to(on, s.rl_on_cmd.shape).astype(I32),
            rl_off_cmd=jnp.broadcast_to(off, s.rl_off_cmd.shape).astype(I32),
            rl_mode_cmd=jnp.broadcast_to(mode, s.rl_mode_cmd.shape).astype(I32),
        )
    if static_bool(pp.rl_enabled) is not False:
        s = apply_rl_commands(s, const, grouped=pp.rl_grouped,
                              enabled=pp.rl_enabled)
    if static_bool(pp.dvfs_enabled) is not False:
        s = apply_dvfs(s, const, terminate_overrun=cfg.terminate_overrun,
                       enabled=pp.dvfs_enabled, rl=pp.dvfs_rl)
    if static_bool(pp.forecast_enabled) is not False:
        s = apply_forecast(s, const,
                           terminate_overrun=cfg.terminate_overrun,
                           enabled=pp.forecast_enabled,
                           dvfs_ramp=pp.forecast_dvfs)
    return s


@jax.named_scope("process_batch")
def process_batch(s: SimState, const: EngineConst, cfg: EngineConfig) -> SimState:
    """One atomic event batch at time s.t (SEMANTICS.md rules 1-8).

    Rules 6-8 (the power-management step) are gated by the traced
    ``const.policy`` flags — this function contains no policy-variant
    branching, static or otherwise.
    """
    with jax.named_scope("complete"):
        s = _complete_jobs(s)
        s = _complete_transitions(s, const)
    with jax.named_scope("scheduler_pass"):
        s = _scheduler_pass(s, const, cfg)
    with jax.named_scope("start_jobs"):
        s = _start_jobs(s, const, cfg)
    with jax.named_scope("power_step"):
        s = _power_step(s, const, cfg)
    return s._replace(n_batches=s.n_batches + 1)


# ---------------------------------------------------------------------------
# time advance
# ---------------------------------------------------------------------------

def _time_candidates(s: SimState, const: EngineConst):
    """Non-transition next-event candidates: (arrivals, finishes, policy).

    Policy candidates (idle-timeout expiries under ``sleep_enabled``, the
    periodic RL tick under ``rl_enabled``) may be <= t; :func:`next_time`
    clamps them strictly-future. Shared by :func:`next_time` and the fused
    :func:`event_horizon` so the two spellings cannot drift.
    """
    t = s.t
    waiting_future = (s.job_status == WAITING) & (s.job_subtime > t)
    arr = jnp.min(jnp.where(waiting_future, s.job_subtime, INF))
    running = s.job_status == RUNNING
    fin = jnp.min(jnp.where(running & (s.job_finish > t), s.job_finish, INF))
    pp = const.policy
    policy_cands = []
    if static_bool(pp.sleep_enabled) is not False:
        idle_unres = (s.node_job < 0) & (s.node_state == IDLE)
        expiry = s.node_idle_since + const.timeout
        policy_cands.append(jnp.min(
            jnp.where(idle_unres & (expiry > t) & pp.sleep_enabled, expiry, INF)
        ))
    if static_bool(pp.rl_enabled) is not False:
        policy_cands.append(
            jnp.where(pp.rl_enabled, t + const.rl_interval, INF)
        )
    if static_bool(pp.forecast_enabled) is not False:
        # rule 10 review tick: re-evaluate the forecast at most one horizon
        # after the last batch, so proactive wake-ups are not gated on an
        # unrelated event landing first. A zero horizon yields c == t,
        # clamped out by next_time — no extra events, the identity case.
        policy_cands.append(jnp.where(
            pp.forecast_enabled & (const.forecast_horizon > 0),
            t + const.forecast_horizon, INF,
        ))
    return arr, fin, policy_cands


def _next_transition(s: SimState) -> jax.Array:
    trans = (s.node_state == SWITCHING_ON) | (s.node_state == SWITCHING_OFF)
    return jnp.min(jnp.where(trans & (s.node_until > s.t), s.node_until, INF))


def next_time(
    s: SimState,
    const: EngineConst,
    cfg: EngineConfig,
    tr: Optional[jax.Array] = None,
) -> jax.Array:
    """Earliest strictly-future event time (INF when none).

    Base candidates (arrivals, finishes, transition completions) plus the
    policy-axis candidates, gated by the traced flags: idle-timeout expiries
    (``sleep_enabled``) and the periodic RL decision tick (``rl_enabled``).
    Policy candidates may be <= t; they are clamped out here so an
    expired-but-guard-blocked candidate can never wedge the clock. With a
    traced flag off (or its interval at INF) a candidate evaluates to
    >= INF and never fires — the superset program needs no static gating;
    a concrete-off flag (specialized path) drops its candidate from the
    trace, which is the same minimum.

    ``tr`` is an optional precomputed transition-completion minimum (the
    fused event pass already has it); i32 min is exact, so passing it is
    bit-identical to recomputing.
    """
    if tr is None:
        tr = _next_transition(s)
    arr, fin, policy_cands = _time_candidates(s, const)
    cands = [arr, fin, tr] + [jnp.where(c > s.t, c, INF) for c in policy_cands]
    return functools.reduce(jnp.minimum, cands).astype(I32)


def _node_power_draw(s: SimState, const: EngineConst) -> jax.Array:
    """f32[N] instantaneous per-node draw — the single spelling shared by
    :func:`accrue_energy` and the fused event pass. Under DVFS an ACTIVE
    node draws its group's current-mode watts (§DVFS)."""
    node_power = jnp.take_along_axis(
        const.power, s.node_state[:, None], axis=1
    )[:, 0]
    dvfs_on = const.policy.dvfs_enabled
    if static_bool(dvfs_on) is not False:
        node_mode = s.dvfs_mode[const.group_id]
        active = s.node_state == ACTIVE
        node_power = jnp.where(
            dvfs_on & active,
            const.dvfs_watts[const.group_id, node_mode],
            node_power,
        )
    return node_power


class EventAux(NamedTuple):
    """Byproducts of the fused event pass, consumed by :func:`accrue_energy`
    and the quiet-batch dispatch (core/SEMANTICS.md §Hot loop). Exactly one
    of ``node_power`` (dense fused-XLA path, bit-exact) / ``draw``
    (kernel or grouped path, per-(group, state) watts) is set; the other is
    None (an empty pytree subtree, so the while-loop carry structure stays
    static). ``occ`` accompanies ``draw`` on the grouped-tables path only
    (§Group-indexed tables): the occupancy histogram the draw was contracted
    from, stored back into ``SimState.occ`` at accrual."""

    node_power: Optional[jax.Array]  # f32[N] per-node draw (XLA path)
    draw: Optional[jax.Array]  # f32[G, 5] per-state draw (kernel/grouped)
    occ: Optional[jax.Array]  # i32[G, 5] occupancy (grouped path only)
    quiet: jax.Array  # bool: next batch is transitions/expiries only


def _fused_kernel_on(cfg: EngineConfig) -> bool:
    """Resolve ``cfg.fused_kernel`` (None = auto: Pallas on TPU only)."""
    if cfg.fused_kernel is not None:
        return bool(cfg.fused_kernel)
    return jax.default_backend() == "tpu"


def _quiet_enabled(const: EngineConst, cfg: EngineConfig) -> bool:
    """Static gate for quiet-event batching: only when the rules a quiet
    batch skips are *statically* absent. RL commands / an in-graph
    controller / DVFS can change state on any batch (pending commands, the
    pressure ladder at mode boundaries), so any of them disables the quiet
    path at trace time; traced (sweep) flags disable it too — a sweep's
    lax.cond would run both branches under vmap anyway."""
    pp = const.policy
    return (
        cfg.fused_events
        and getattr(cfg.policy, "controller", None) is None
        and static_bool(pp.rl_enabled) is False
        and static_bool(pp.dvfs_enabled) is False
        # rule 10's EWMA predictor must update on every batch, quiet or not
        and static_bool(pp.forecast_enabled) is False
    )


@jax.named_scope("quiet_batch")
def _quiet_batch(s: SimState, const: EngineConst, cfg: EngineConfig) -> SimState:
    """Stripped batch for quiet events (§Hot loop): transition completions
    and idle-timeout expiries only — no window scatter, no argsorts, no
    shadow machinery.

    Only dispatched when ``EventAux.quiet`` proved the full batch is a
    no-op outside rules 2 and 6 (no finishes or arrivals at the new t, no
    waiting-arrived or ALLOCATED jobs), and rule 6 degenerates to
    "switch off every expired candidate": with an empty queue the IPM
    demand cap ``max(avail - demand, 0) = avail >= n_cand`` and the no-cap
    path allows N, so ``timeout_switch_off``'s k-longest-idle selection
    selects every candidate — the argsort is dead. Rule 7 is a no-op for
    the same reason (deficit = -avail <= 0). Bit-exact with
    :func:`process_batch` on such batches; safe (pure no-op arithmetic) on
    any state, as vmapped ``lax.cond`` runs both branches.
    """
    s = _complete_transitions(s, const)
    pp = const.policy
    if static_bool(pp.sleep_enabled) is not False:
        cand = (
            (s.node_job < 0)
            & (s.node_state == IDLE)
            & (s.t - s.node_idle_since >= const.timeout)
        )
        if static_bool(pp.sleep_enabled) is None:
            cand = cand & pp.sleep_enabled
        s = s._replace(
            node_state=jnp.where(cand, SWITCHING_OFF, s.node_state),
            node_until=jnp.where(cand, s.t + const.t_off, s.node_until),
            n_switch_off=s.n_switch_off + jnp.sum(cand, dtype=I32),
        )
    return s._replace(n_batches=s.n_batches + 1)


@jax.named_scope("event_horizon")
def event_horizon(
    s: SimState, const: EngineConst, cfg: EngineConfig
) -> Tuple[jax.Array, EventAux]:
    """The fused event pass (§Hot loop): one read of the node arrays yields
    the next-event time AND the power draw for the coming accrual interval
    (plus the quiet-batch classification), where the legacy loop read them
    twice per iteration (``next_time`` in cond + body, ``accrue_energy``
    again).

    Kernel routing: on TPU (or ``cfg.fused_kernel=True``) the
    histogram + masked-min pair runs through the Pallas ``event_fuse``
    kernel — gated to single-group platforms with DVFS statically off,
    where ``const.power[0]`` IS the per-state table (make_const broadcasts
    one row per group). The i32 transition min is exact either way; the
    kernel's per-state f32 sums differ from the engine's scatter-add only
    in reduction order, so the kernel path is schedule-bit-exact with
    energy equal to rounding (energy never feeds back into scheduling).
    The default CPU path computes the draw via :func:`_node_power_draw` —
    the identical expression ``accrue_energy`` used to inline, so it is
    bit-exact, and the fusion win is reuse, not rewriting.

    Grouped tables (§Group-indexed tables) lift the single-group kernel
    gate: the pass reduces the node arrays to the [G, 5] occupancy
    histogram (Pallas ``event_fuse_occ`` on TPU — counts are exact in f32
    — or one XLA scatter-add) and contracts it with the [G, 5] group power
    table via :func:`_group_draw`, DVFS included; every downstream consumer
    is then G-sized.
    """
    pp = const.policy
    G = s.energy.shape[0]
    aux_occ = None
    if cfg.grouped_tables:
        if _fused_kernel_on(cfg):
            from repro.kernels import ops  # lazy: keep engine importable alone

            occ8, tr_v = ops.event_fuse_occ(
                s.node_state[None], s.node_until[None], s.t[None],
                const.group_id, G,
            )
            aux_occ = occ8[0, :, :N_STATES].astype(I32)
            tr = tr_v[0]
        else:
            aux_occ = _occupancy(s, const)
            tr = _next_transition(s)
        aux_power, aux_draw = None, _group_draw(s, aux_occ, const)
    else:
        use_kernel = (
            _fused_kernel_on(cfg)
            and G == 1
            and static_bool(pp.dvfs_enabled) is False
        )
        if use_kernel:
            from repro.kernels import ops  # lazy: keep engine importable alone

            draw8, tr_v = ops.event_fuse_ledger(
                s.node_state[None], s.node_until[None], s.t[None],
                const.power[0],
            )
            aux_power, aux_draw = None, draw8[:, :N_STATES]
            tr = tr_v[0]
        else:
            aux_power, aux_draw = _node_power_draw(s, const), None
            tr = _next_transition(s)
    arr, fin, policy_cands = _time_candidates(s, const)
    cands = [arr, fin, tr] + [jnp.where(c > s.t, c, INF) for c in policy_cands]
    nt = functools.reduce(jnp.minimum, cands).astype(I32)
    if _quiet_enabled(const, cfg):
        busy = jnp.any(
            ((s.job_status == WAITING) & (s.job_subtime <= s.t))
            | (s.job_status == ALLOCATED)
        )
        quiet = (arr > nt) & (fin > nt) & ~busy
    else:
        quiet = jnp.asarray(False)
    return nt, EventAux(
        node_power=aux_power, draw=aux_draw, occ=aux_occ, quiet=quiet
    )


@jax.named_scope("accrue_energy")
def accrue_energy(
    s: SimState,
    t_next: jax.Array,
    const: EngineConst,
    aux: Optional[EventAux] = None,
) -> SimState:
    dt = jnp.maximum(t_next - s.t, 0).astype(jnp.float32)
    dvfs_on = const.policy.dvfs_enabled
    dvfs_b = static_bool(dvfs_on)
    mode_time, mode_energy = s.mode_time, s.mode_energy
    occ_new = None
    if aux is not None and aux.occ is not None:
        # grouped fused path (§Group-indexed tables): the [G, 5] draw is
        # already contracted from the occupancy histogram; the DVFS mode
        # ledgers come from the same G-sized quantities (the draw's ACTIVE
        # column is the group's current-mode watts by construction)
        occ_new = aux.occ
        delta = aux.draw * dt
        if dvfs_b is not False:
            G = s.energy.shape[0]
            gi = jnp.arange(G)
            mode_time = s.mode_time.at[gi, s.dvfs_mode].add(
                jnp.where(dvfs_on, dt, 0.0)
            )
            mode_energy = s.mode_energy.at[gi, s.dvfs_mode].add(
                jnp.where(dvfs_on, aux.draw[:, ACTIVE] * dt, 0.0)
            )
    elif const.tables is not None:
        # grouped legacy loop: the identical expressions as the fused
        # spelling above (_occupancy + _group_draw), so the two grouped
        # loop shapes are fully bit-exact, energy included
        occ_new = _occupancy(s, const)
        draw = _group_draw(s, occ_new, const)
        delta = draw * dt
        if dvfs_b is not False:
            G = s.energy.shape[0]
            gi = jnp.arange(G)
            mode_time = s.mode_time.at[gi, s.dvfs_mode].add(
                jnp.where(dvfs_on, dt, 0.0)
            )
            mode_energy = s.mode_energy.at[gi, s.dvfs_mode].add(
                jnp.where(dvfs_on, draw[:, ACTIVE] * dt, 0.0)
            )
    elif aux is not None and aux.draw is not None:
        # fused-kernel path: the per-(group, state) draw is already reduced
        # on device; only reachable with DVFS statically off (§Hot loop), so
        # the mode ledgers stay untouched by construction
        assert dvfs_b is False
        delta = aux.draw * dt
    else:
        # per-node draw scattered into the [G, 5] group x state ledger —
        # reused from the fused event pass when available (identical
        # expression, so carrying it is bit-exact)
        if aux is not None and aux.node_power is not None:
            node_power = aux.node_power
        else:
            node_power = _node_power_draw(s, const)
        delta = (
            jnp.zeros_like(s.energy)
            .at[const.group_id, s.node_state]
            .add(node_power)
            * dt
        )
        # DVFS ledgers: per-group mode residency and ACTIVE energy by mode
        # (skipped under a concrete-off flag: accruing zero is the identity)
        if dvfs_b is not False:
            node_mode = s.dvfs_mode[const.group_id]
            active = s.node_state == ACTIVE
            G = s.energy.shape[0]
            mode_time = s.mode_time.at[jnp.arange(G), s.dvfs_mode].add(
                jnp.where(dvfs_on, dt, 0.0)
            )
            mode_energy = s.mode_energy.at[const.group_id, node_mode].add(
                jnp.where(dvfs_on & active, node_power * dt, 0.0)
            )
    e, c = _kahan_add(s.energy, s.energy_c, delta)
    n_waiting = jnp.sum(
        ((s.job_status == WAITING) & (s.job_subtime <= s.t))
        | (s.job_status == ALLOCATED),
        dtype=jnp.float32,
    )
    w, wc = _kahan_add(s.wait_integral, s.wait_c, n_waiting * dt)
    return s._replace(
        energy=e, energy_c=c, mode_time=mode_time, mode_energy=mode_energy,
        wait_integral=w, wait_c=wc,
        occ=s.occ if occ_new is None else occ_new,
    )


def all_done(s: SimState) -> jax.Array:
    return jnp.all(s.job_status == DONE)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def default_batch_cap(n_jobs: int) -> int:
    return 20 * n_jobs + 10_000


def trim_window(config: EngineConfig, n_jobs: int) -> EngineConfig:
    """Shrink the static scheduler window to what the workload can fill.

    The queue can never hold more than the workload's job count, so any
    window slot past ``n_jobs`` is provably a -1-padding no-op in every
    batch — ``_queue_window`` still scattered into it and the legacy
    ``fori_loop`` still attempted it (core/SEMANTICS.md §Hot loop). A
    tighter bound does NOT follow from ``job_subtime`` alone: on a
    saturated cluster jobs pile up WAITING long past their submission, so
    any submission-overlap prepass under-counts the queue; ``n_jobs`` is
    the largest sound static bound. Bit-exact by construction; applied by
    the :func:`simulate` / :func:`sweep` / RL-env drivers (the pydes twin
    slices its window from a dynamic queue list, so trimming is a no-op
    there).
    """
    W = max(1, min(config.window, n_jobs))
    if W == config.window:
        return config
    return dataclasses.replace(config, window=W)


def run_sim(
    s: SimState,
    const: EngineConst,
    cfg: EngineConfig,
    max_batches: Optional[int] = None,
) -> SimState:
    """Run to completion (jit-able; vmap over s and/or const).

    ``truncated`` is set on the returned state when the batch cap stopped
    the run with future events still pending — metrics from such a state
    describe a partial simulation, not a finished one.

    Under ``cfg.fused_events`` (the default; core/SEMANTICS.md §Hot loop)
    each iteration runs ONE fused event pass (:func:`event_horizon`) whose
    next-event time rides the loop carry — the legacy loop recomputed
    ``next_time`` in both cond and body and re-read the node arrays again
    in ``accrue_energy``. Quiet batches (pure transition completions /
    timeout expiries) dispatch to the stripped :func:`_quiet_batch` instead
    of the full scheduler pass. Bit-exact either way.
    """
    # spars-lint: ignore[SL001] resolved into the jit key's explicit `cap`
    # argument before lookup — never read inside the compiled body
    cap = max_batches or cfg.max_batches or default_batch_cap(
        int(s.job_status.shape[0])
    )

    s = process_batch(s, const, cfg)

    if not cfg.fused_events:  # legacy loop: the benchmarkable baseline
        def cond(s):
            nt = next_time(s, const, cfg)
            return (~all_done(s)) & (nt < INF) & (s.n_batches < cap)

        def body(s):
            nt = next_time(s, const, cfg)
            s = accrue_energy(s, nt, const)
            s = s._replace(t=nt)
            return process_batch(s, const, cfg)

        with jax.named_scope("loop"):
            out = jax.lax.while_loop(cond, body, s)
        # cap-hit detection: the loop would have continued but for n_batches
        nt = next_time(out, const, cfg)
        return out._replace(truncated=(~all_done(out)) & (nt < INF))

    quiet_on = _quiet_enabled(const, cfg)
    nt0, aux0 = event_horizon(s, const, cfg)

    def cond(carry):
        s, nt, _ = carry
        return (~all_done(s)) & (nt < INF) & (s.n_batches < cap)

    def body(carry):
        s, nt, aux = carry
        s = accrue_energy(s, nt, const, aux=aux)
        s = s._replace(t=nt)
        if quiet_on:
            s = jax.lax.cond(
                aux.quiet,
                lambda s_: _quiet_batch(s_, const, cfg),
                lambda s_: process_batch(s_, const, cfg),
                s,
            )
        else:
            s = process_batch(s, const, cfg)
        nt, aux = event_horizon(s, const, cfg)
        return s, nt, aux

    # the loop's own ops (its condition, the carry) fall in no other phase
    with jax.named_scope("loop"):
        out, nt, _ = jax.lax.while_loop(cond, body, (s, nt0, aux0))
    # cap-hit detection: the loop would have continued but for n_batches
    return out._replace(truncated=(~all_done(out)) & (nt < INF))


def run_sim_gantt(
    s: SimState,
    const: EngineConst,
    cfg: EngineConfig,
    max_batches: int,
) -> Tuple[SimState, GanttLog]:
    """Like run_sim but records per-batch node-state snapshots for Gantt.

    ``max_batches`` is also the log capacity; a cap-stopped run comes back
    with ``state.truncated`` set (the Gantt log is then a prefix, not the
    whole schedule).
    """
    N = s.node_state.shape[0]
    log = GanttLog(
        t0=jnp.zeros(max_batches, I32),
        t1=jnp.zeros(max_batches, I32),
        state=jnp.zeros((max_batches, N), I32),
        job=jnp.zeros((max_batches, N), I32),
        n=jnp.asarray(0, I32),
    )

    s = process_batch(s, const, cfg)

    def cond(carry):
        s, log = carry
        nt = next_time(s, const, cfg)
        return (~all_done(s)) & (nt < INF) & (s.n_batches < max_batches)

    def body(carry):
        s, log = carry
        nt = next_time(s, const, cfg)
        i = log.n
        log = log._replace(
            t0=log.t0.at[i].set(s.t),
            t1=log.t1.at[i].set(nt),
            state=log.state.at[i].set(s.node_state),
            job=log.job.at[i].set(jnp.where(s.node_state == ACTIVE, s.node_job, -1)),
            n=i + 1,
        )
        s = accrue_energy(s, nt, const)
        s = s._replace(t=nt)
        s = process_batch(s, const, cfg)
        return s, log

    with jax.named_scope("loop"):
        out, log = jax.lax.while_loop(cond, body, (s, log))
    nt = next_time(out, const, cfg)
    out = out._replace(truncated=(~all_done(out)) & (nt < INF))
    return out, log


# convenience: one-call host API ------------------------------------------------

# jitted single-run programs, keyed like _SWEEP_FNS on the static trace
# inputs (window, node_order, terminate_overrun, in-graph controller,
# shapes, batch cap) PLUS the specialization mode: the concrete
# PolicyParams when specialized (one cached program per policy point),
# None for the traced superset. Bounded LRU — repeated simulate() calls
# with identical static structure reuse the compiled program instead of
# recompiling per call.
_SIM_FNS: "OrderedDict" = OrderedDict()
_SIM_CACHE_SIZE = 8


def _static_trace_key(platform, config, J, cap):
    """Every static trace input of a run_sim program, in one place — the
    shared prefix of the simulate and sweep jit-cache keys (a field missed
    in one of two copies would silently reuse a program compiled for a
    different config)."""
    return (
        config.window, config.node_order, config.terminate_overrun,
        getattr(config.policy, "controller", None),
        # the controller-arity guard in _power_step reads policy.dvfs
        # statically, so it is trace structure alongside the controller
        getattr(config.policy, "dvfs", False),
        # hot-loop structure (§Hot loop): the loop shape and the resolved
        # kernel routing are trace structure
        config.fused_events, _fused_kernel_on(config),
        # §Group-indexed tables: the grouped/dense path choice and the
        # burst-merging pass-repeat loop are trace structure
        config.grouped_tables, config.merge_bursts,
        # §Partition-aware allocation: the per-group selection spelling in
        # _try_allocate is a Python branch, hence trace structure
        config.allocation,
        # §Device-sharded sweeps: the default sweep device count selects
        # the sharded vs single-device dispatch of the same program
        config.devices,
        platform.nb_nodes, platform.n_groups(), platform.n_dvfs_modes(),
        J, cap,
    )


def _warn_truncated(state: SimState, what: str) -> None:
    if bool(np.asarray(state.truncated).any()):
        warnings.warn(
            f"{what} hit its batch cap before completing — the returned "
            "state/metrics describe a PARTIAL simulation (SimState.truncated"
            " / SimMetrics.truncated). Raise EngineConfig.max_batches (or "
            "pass max_batches) to run to completion.",
            RuntimeWarning,
            stacklevel=3,
        )


def simulate(
    platform: PlatformSpec,
    workload: Workload,
    config: EngineConfig,
    job_capacity: Optional[int] = None,
    jit: bool = True,
    specialize: bool = True,
    return_compiles: bool = False,
) -> Union[SimState, Tuple[SimState, Optional[int]]]:
    """Run ONE configuration to completion (the single-config fast path).

    By default the run is *statically specialized* (core/SEMANTICS.md
    §Static specialization): the policy flags are folded in as closure
    constants, so XLA dead-code-eliminates the rules the policy turned off
    — bit-exact with the traced superset program (``specialize=False``)
    that :func:`sweep` uses for one-compile grids. Compiled programs are
    cached in a bounded LRU keyed on the static trace structure, so
    repeated calls with the same shapes/config compile exactly once.

    ``return_compiles=True`` additionally returns the cumulative compile
    count of the cached program (None on JAX versions without the
    introspection API) — the no-recompile guarantee for experiment layers.
    """
    config = trim_window(config, len(workload))
    s = init_state(platform, workload, config, job_capacity=job_capacity)
    # specialized: the policy rides as concrete bools (no device scalars),
    # lifted out below as the closure constant of the cached program
    const = make_const(platform, config, specialize=specialize)
    cap = config.max_batches or default_batch_cap(len(workload))
    n_compiles = None
    if not jit:
        out = run_sim(s, const, config, max_batches=cap)
    else:
        static_pp = const.policy if specialize else None
        key = _static_trace_key(
            platform, config, int(s.job_status.shape[0]), cap
        ) + (static_pp,)
        fn = _SIM_FNS.pop(key, None)
        if fn is None:
            if len(_SIM_FNS) >= _SIM_CACHE_SIZE:
                _SIM_FNS.popitem(last=False)  # evict least-recently-used
            if static_pp is None:
                fn = jax.jit(
                    lambda s_, c_: run_sim(s_, c_, config, max_batches=cap)
                )
            else:
                # the traced const carries policy=None; the concrete flags
                # are reinserted inside the trace as closure constants
                fn = jax.jit(
                    lambda s_, c_: run_sim(
                        s_, c_._replace(policy=static_pp), config,
                        max_batches=cap,
                    )
                )
        _SIM_FNS[key] = fn
        out = fn(s, const._replace(policy=None) if static_pp else const)
        cache_size = getattr(fn, "_cache_size", None)
        n_compiles = cache_size() if callable(cache_size) else None
    _warn_truncated(out, f"simulate({config.label()!r})")
    if return_compiles:
        return out, n_compiles
    return out


# batched sweep driver -----------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SimBatch:
    """Result of :func:`sweep`: K scenarios run as one compiled program.

    ``states`` is the stacked final :class:`SimState` (leading axis K);
    ``metrics[i]`` the i-th scenario's :class:`SimMetrics`. ``n_compiles``
    is the cumulative compile count of the underlying jitted program (None
    on JAX versions without the ``_cache_size`` introspection API) — the
    no-recompile guarantee asserted by ``benchmarks/bench_scale.py``.
    """

    states: SimState
    metrics: Tuple[SimMetrics, ...]
    n_compiles: Optional[int]
    # §Device-sharded sweeps: whether this launch reused an already-compiled
    # grid program from the _SWEEP_FNS LRU (the service layer's per-request
    # cache report), and the device count it ran sharded across (None =
    # unsharded single-device dispatch)
    cache_hit: Optional[bool] = None
    devices: Optional[int] = None

    def __len__(self) -> int:
        return len(self.metrics)

    def __getitem__(self, i: int) -> SimMetrics:
        return self.metrics[i]

    def state_at(self, i: int) -> SimState:
        return jax.tree_util.tree_map(lambda a: a[i], self.states)

    def rows(self) -> Tuple[dict, ...]:
        return tuple(m.row() for m in self.metrics)


# jitted sweep programs, keyed by the *static* trace inputs only (window,
# node_order, terminate_overrun, in-graph controller, shapes, batch cap,
# grid width). The policy axis and every platform value are traced operands,
# so sweeps over different scheduler/policy/timeout grids share one entry.
# Bounded LRU: long-lived grid-search processes must not accumulate
# compiled programs without limit.
_SWEEP_FNS: "OrderedDict" = OrderedDict()
_SWEEP_CACHE_SIZE = 8

# compiled-grid reuse ledger (§Device-sharded sweeps): one hit/miss tick
# per sweep dispatch against the _SWEEP_FNS LRU. The service layer
# (launch/sim_serve.py) snapshots this around each request to report
# compile-cache reuse in its response JSON.
_CACHE_STATS = {"sweep_hits": 0, "sweep_misses": 0}


def cache_stats() -> dict:
    """A copy of the sweep compile-cache hit/miss counters."""
    return dict(_CACHE_STATS)


def _resolve_devices(devices, config: EngineConfig) -> Optional[int]:
    """Resolve the sweep device count (§Device-sharded sweeps).

    ``None`` falls back to ``config.devices``; ``None`` overall keeps the
    unsharded single-device dispatch (the legacy ``jit(vmap)`` path).
    ``"all"`` takes every visible device; an int ``D`` shards across the
    first ``D`` local devices (1 <= D <= ``jax.device_count()``).
    """
    if devices is None:
        devices = config.devices
    if devices is None:
        return None
    if devices == "all":
        return jax.device_count()
    d = int(devices)
    if d < 1:
        raise ValueError(f"devices must be >= 1, got {devices!r}")
    if d > jax.device_count():
        raise ValueError(
            f"devices={d} exceeds the {jax.device_count()} visible "
            "device(s); set XLA_FLAGS=--xla_force_host_platform_device_"
            "count=<D> before JAX initializes to fake host devices"
        )
    return d


def _policy_scenario_const(
    base, policy: PowerPolicy, const: EngineConst, config: EngineConfig
) -> EngineConst:
    """Lower a (base, policy) scenario point onto the traced policy axis."""
    if getattr(policy, "controller", None) is not None and (
        policy.controller is not getattr(config.policy, "controller", None)
    ):
        raise ValueError(
            "sweep scenarios cannot carry their own in-graph RL controller "
            "(a callable is static trace structure, not a traced operand); "
            "set the controller on the sweep's config instead"
        )
    return const._replace(policy=policy.params(base).traced())


def _scenario_const(
    scenario, base_const: EngineConst, platform: PlatformSpec, config: EngineConfig
) -> Tuple[EngineConst, PlatformSpec]:
    if isinstance(scenario, EngineConst):
        return scenario, platform
    if isinstance(scenario, PlatformSpec):
        if (
            scenario.nb_nodes != platform.nb_nodes
            or scenario.n_groups() != platform.n_groups()
            or scenario.n_dvfs_modes() != platform.n_dvfs_modes()
        ):
            raise ValueError(
                "sweep platforms must share node count, group count, and "
                "DVFS mode-table width "
                f"(base {platform.nb_nodes} nodes/{platform.n_groups()} "
                f"groups/{platform.n_dvfs_modes()} modes, scenario "
                f"{scenario.nb_nodes}/{scenario.n_groups()}/"
                f"{scenario.n_dvfs_modes()}); shapes are part of the "
                "compiled program"
            )
        return make_const(scenario, config), scenario
    if isinstance(scenario, str):  # scheduler label, e.g. "EASY PSAS+IPM"
        b, pol = from_label(scenario)
        return _policy_scenario_const(b, pol, base_const, config), platform
    if isinstance(scenario, PowerPolicy):
        return (
            _policy_scenario_const(config.base, scenario, base_const, config),
            platform,
        )
    if isinstance(scenario, Mapping):
        sc = dict(scenario)
        plat, const = platform, base_const
        if "platform" in sc:
            p = sc.pop("platform")
            if not isinstance(p, PlatformSpec):
                raise TypeError(
                    f"scenario 'platform' must be a PlatformSpec, got {p!r}"
                )
            const, plat = _scenario_const(p, base_const, platform, config)
        base, pol = config.base, config.policy
        if "scheduler" in sc:
            base, pol = from_label(sc.pop("scheduler"))
        base = sc.pop("base", base)
        pol = sc.pop("policy", pol)
        const = _policy_scenario_const(base, pol, const, config)
        if "timeout" in sc:
            t = sc.pop("timeout")
            t = int(INF_TIME) if t is None else int(t)
            const = const._replace(timeout=jnp.asarray(t, I32))
        if "tables" in sc:
            raise TypeError(
                "sweep scenarios cannot override 'tables' directly — the "
                "grouped tables are derived from the platform "
                "(core/tables.py); pass a PlatformSpec scenario instead"
            )
        unknown = sorted(k for k in sc if k not in EngineConst._fields)
        if unknown:
            raise TypeError(
                f"unknown sweep scenario key(s) {unknown}: expected "
                "scheduler/base/policy/timeout/platform or EngineConst "
                f"fields {EngineConst._fields}"
            )
        over = {}
        for k, v in sc.items():
            ref = getattr(const, k)
            try:
                # normalize to the field's dtype and per-node shape now, so
                # a bad value fails here (naming the key) instead of deep
                # inside jnp.stack/vmap
                over[k] = jnp.broadcast_to(jnp.asarray(v, ref.dtype), ref.shape)
            except (TypeError, ValueError) as e:
                raise TypeError(
                    f"invalid value for sweep scenario key {k!r} "
                    f"(EngineConst field of shape {ref.shape}, dtype "
                    f"{ref.dtype}): {e}"
                ) from e
        return const._replace(**over), plat
    if scenario is None or isinstance(scenario, (int, np.integer)):
        t = int(INF_TIME) if scenario is None else int(scenario)
        return base_const._replace(timeout=jnp.asarray(t, I32)), platform
    raise TypeError(
        f"unsupported sweep scenario {scenario!r}: expected an int timeout, "
        "None, a scheduler label, a PowerPolicy, a PlatformSpec, an "
        "EngineConst, or a mapping of scenario overrides"
    )


@dataclasses.dataclass
class PendingSweep:
    """An in-flight :func:`sweep_async` dispatch (§Device-sharded sweeps).

    The compiled grid program has been launched (JAX dispatch is
    asynchronous — the device arrays inside are futures); host work can
    overlap with the device computation until :meth:`result` blocks. The
    streaming experiment runner dispatches chunk ``k+1`` before draining
    chunk ``k`` through this handle; :meth:`result` then gathers the
    stacked final state to the host in one transfer.
    """

    _out: SimState  # padded stacked final states (leading axis K + pad)
    _plats: list
    _k: int  # requested scenario count (pad rows dropped on gather)
    _n_compiles: Optional[int]
    _cache_hit: bool
    _devices: Optional[int]
    _span: spans.Begun  # the ``sweep`` span, begun by sweep_async
    _batch: Optional[SimBatch] = None

    def result(self) -> SimBatch:
        """Block on the device computation and build the :class:`SimBatch`
        (idempotent — the batch is cached after the first call)."""
        if self._batch is not None:
            return self._batch
        try:
            with spans.within(self._span):
                with spans.span("sweep.wait"):
                    jax.block_until_ready(self._out.energy)
                with spans.span("sweep.gather"):
                    self._batch = self._gather()
        finally:
            spans.end(self._span)
        return self._batch

    def _gather(self) -> SimBatch:
        """One host transfer of the stacked final state, then every lane's
        metrics from numpy views of that copy (§Device-sharded sweeps).

        ``jax.device_get`` starts each leaf's device-to-host copy before
        collecting any (a sharded leaf is assembled from its shards), so a
        gather is one round of transfers, not one per lane and field. Peak
        host memory is K × (state bytes per lane). ``SimBatch.states``
        stays the device tree with the pad rows dropped.
        """
        k = self._k
        out = self._out
        host = jax.tree_util.tree_map(lambda a: a[:k], jax.device_get(out))
        if int(out.energy.shape[0]) != k:  # drop masked pad rows
            out = jax.tree_util.tree_map(lambda a: a[:k], out)
        trunc = np.flatnonzero(host.truncated)
        if trunc.size:
            warnings.warn(
                f"sweep scenario(s) {[int(i) for i in trunc]} hit the batch "
                "cap before completing — their rows describe PARTIAL "
                "simulations (SimMetrics.truncated). Raise "
                "EngineConfig.max_batches to run them to completion.",
                RuntimeWarning,
                stacklevel=2,
            )

        from repro.core.metrics import metrics_from_state  # import cycle

        metrics = tuple(
            metrics_from_state(
                jax.tree_util.tree_map(lambda a, i=i: a[i], host),
                self._plats[i],
            )
            for i in range(k)
        )
        return SimBatch(
            states=out, metrics=metrics, n_compiles=self._n_compiles,
            cache_hit=self._cache_hit, devices=self._devices,
        )


def _sweep_program(config: EngineConfig, cap: int, devices=None):
    """The jitted grid program: :func:`run_sim` vmapped over the stacked
    scenario axis (``s0`` shared), unsharded when ``devices`` is None.

    Given a device list, the scenario axis is lowered onto a 1-D mesh over
    it: each device runs the identical vmapped program over its (K+pad)/D
    scenario rows; s0 is replicated. vmap is elementwise per scenario, so
    per-scenario results are bit-exact vs the unsharded dispatch
    (§Device-sharded sweeps)."""
    run_k = jax.vmap(
        lambda s, c: run_sim(s, c, config, max_batches=cap),
        in_axes=(None, 0),
    )
    if devices is None:
        return jax.jit(run_k)
    mesh = jax.sharding.Mesh(np.asarray(devices), ("scenario",))
    sharded = jax.sharding.PartitionSpec("scenario")
    return jax.jit(
        jax.shard_map(
            run_k,
            mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(), sharded),
            out_specs=sharded,
            check_vma=False,
        )
    )


def sweep_async(
    platform: PlatformSpec,
    workload: Workload,
    scenarios: Sequence[Any],
    config: Optional[EngineConfig] = None,
    job_capacity: Optional[int] = None,
    devices: Optional[Any] = None,
) -> PendingSweep:
    """Dispatch K scenarios without blocking (the overlap spelling of
    :func:`sweep` — same arguments, same compiled program, same cache).

    Returns a :class:`PendingSweep` whose ``result()`` blocks and builds
    the :class:`SimBatch`. Dispatching the next chunk before draining the
    previous one overlaps host transfer with device compute — the
    streaming experiment runner's pipeline.
    """
    config = trim_window(config or EngineConfig(), len(workload))
    scenarios = list(scenarios)
    if not scenarios:
        raise ValueError("sweep needs at least one scenario")
    # the ``sweep`` span runs on until the returned handle's result()
    top = spans.begin("sweep")
    try:
        with spans.within(top):
            return _dispatch_sweep(
                top, platform, workload, scenarios, config, job_capacity,
                devices,
            )
    except BaseException:
        spans.end(top)
        raise


def _dispatch_sweep(
    top, platform, workload, scenarios, config, job_capacity, devices
) -> PendingSweep:
    with spans.span("sweep.consts"):
        base_const = make_const(platform, config)
        consts, plats = [], []
        for sc in scenarios:
            c, p = _scenario_const(sc, base_const, platform, config)
            consts.append(c)
            plats.append(p)
    with spans.span("sweep.stack"):
        K = len(consts)
        D = _resolve_devices(devices, config)
        pad = 0 if D is None else (-K) % D
        if pad:
            # §Device-sharded sweeps pad/mask rule: pad rows reuse scenario
            # 0's const so they trace identically to real rows; dropped on
            # gather
            consts = consts + [consts[0]] * pad
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *consts)
    with spans.span("sweep.init"):
        s0 = init_state(platform, workload, config, job_capacity=job_capacity)
    with spans.span("sweep.dispatch"):
        cap = config.max_batches or default_batch_cap(len(workload))
        # the cache key grows the padded grid width and the device count,
        # so a sharded grid never reuses (or poisons) an unsharded
        # program's entry
        key = _static_trace_key(
            platform, config, int(s0.job_status.shape[0]), cap
        ) + (K + pad, D)
        fn = _SWEEP_FNS.pop(key, None)
        cache_hit = fn is not None
        _CACHE_STATS["sweep_hits" if cache_hit else "sweep_misses"] += 1
        if fn is None:
            if len(_SWEEP_FNS) >= _SWEEP_CACHE_SIZE:
                _SWEEP_FNS.popitem(last=False)  # evict least-recently-used
            fn = _sweep_program(
                config, cap, None if D is None else jax.devices()[:D]
            )
        _SWEEP_FNS[key] = fn
        out = fn(s0, stacked)  # asynchronous dispatch — not blocked here
        cache_size = getattr(fn, "_cache_size", None)
        n_compiles = cache_size() if callable(cache_size) else None
    return PendingSweep(out, plats, K, n_compiles, cache_hit, D, top)


def sweep(
    platform: PlatformSpec,
    workload: Workload,
    scenarios: Sequence[Any],
    config: Optional[EngineConfig] = None,
    job_capacity: Optional[int] = None,
    devices: Optional[Any] = None,
) -> SimBatch:
    """Run K scenarios as ONE compiled program (vmapped :func:`run_sim`).

    A scenario is a point on the traced axes of :class:`EngineConst` —
    including the policy axis — sharing only ``config``'s static structure
    (window, node_order, terminate_overrun, in-graph RL controller):

    * an int (timeout override; ``None`` = never),
    * a scheduler label string (``"FCFS PSAS+IPM"`` — the ``from_label``
      registry), replacing base *and* power policy,
    * a :class:`~repro.core.policy.PowerPolicy` (keeps ``config.base``),
    * a :class:`PlatformSpec` with the same node/group counts (full
      per-node power/speed/delay tables are traced operands),
    * a mapping combining any of the above under the keys ``scheduler`` /
      ``base`` / ``policy`` / ``timeout`` / ``platform``, plus raw
      :class:`EngineConst` field overrides — the form
      ``repro.experiments`` builds its grids from,
    * or a prebuilt :class:`EngineConst`.

    The stacked consts are vmapped over, so the whole
    scheduler x policy x timeout x platform grid compiles ONCE (the paper's
    Figs. 4/5 six-scheduler comparison is one program, not six);
    per-scenario :class:`SimMetrics` come back in a :class:`SimBatch`.

    ``devices`` (§Device-sharded sweeps) shards the scenario axis across
    local devices via a 1-D mesh: an int ``D``, ``"all"``, or ``None``
    (fall back to ``config.devices``; unsharded when that is None too).
    The scenario axis is padded to a device multiple with masked rows
    (dropped on gather); per-scenario results are **bit-exact** vs the
    unsharded dispatch, and the grid still compiles ONCE.
    """
    return sweep_async(
        platform, workload, scenarios, config,
        job_capacity=job_capacity, devices=devices,
    ).result()
