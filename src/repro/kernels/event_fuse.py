"""Pallas-TPU fused event-batch reduction for the vectorized SPARS engine.

The hot loop of the paper's system, once vmapped over thousands of RL
environments, is the per-batch pair

    accrue_energy : power_draw(t) = Σ_n power[state_n]      (histogram)
    next_time     : min over switching nodes of until_n      (masked min)

Each is a bandwidth-bound reduction over the node arrays (the engine reads
``node_state``/``node_until`` twice per event batch). This kernel fuses the
two into ONE pass over the node arrays — per env-block it reads the i32
state/until rows once from HBM into VMEM and emits both reductions:

    power_draw [E, 1] f32 : instantaneous power at time t
    next_trans [E, 1] i32 : earliest strictly-future transition completion

Grid ``(E/bE,)``; block (bE, N). N is the node count — padded to a lane
multiple (128) by the wrapper with PAD_STATE (histogram weight 0, masked out
of the min). The per-state power table is a (1, 8) VMEM operand (5 states
padded to 8) broadcast to every grid step.

Arithmetic intensity ≈ (5 compares + 5 FMAs + 1 select) per 8 bytes —
firmly memory-bound; the win over the XLA pair is the halved HBM traffic
(one read of each row instead of two), which the roofline model in
EXPERIMENTS.md §Perf quantifies for the spars-rl cell.

Oracle: ``ref.event_fuse_reference``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import INF_TIME, N_STATES, SWITCHING_OFF, SWITCHING_ON

PAD_STATE = 7  # padding nodes: zero power, never transitioning
LANES = 128


def _event_kernel(
    state_ref,  # (bE, N) i32
    until_ref,  # (bE, N) i32
    t_ref,  # (bE, 1) i32
    power_ref,  # (1, 8) f32
    draw_ref,  # (bE, 1) f32
    next_ref,  # (bE, 1) i32
):
    state = state_ref[...]
    until = until_ref[...]
    t = t_ref[...]  # (bE, 1)

    # --- fused histogram: power_draw = sum_n power[state_n] ---
    draw = jnp.zeros(state.shape, jnp.float32)
    for s in range(N_STATES):
        draw = draw + jnp.where(state == s, power_ref[0, s], 0.0)
    draw_ref[...] = jnp.sum(draw, axis=1, keepdims=True)

    # --- fused masked min: next strictly-future transition completion ---
    switching = jnp.logical_or(state == SWITCHING_ON, state == SWITCHING_OFF)
    future = until > t  # (bE, N) broadcast over nodes
    masked = jnp.where(
        jnp.logical_and(switching, future), until, jnp.int32(INF_TIME)
    )
    next_ref[...] = jnp.min(masked, axis=1, keepdims=True)


def _event_ledger_kernel(
    state_ref,  # (bE, N) i32
    until_ref,  # (bE, N) i32
    t_ref,  # (bE, 1) i32
    power_ref,  # (1, 8) f32
    draw_ref,  # (bE, 8) f32 per-state power sums
    next_ref,  # (bE, 1) i32
):
    """Ledger variant: per-STATE power sums instead of the scalar total.

    The engine's energy accounting is a [G, 5] group x state ledger; on a
    single-group platform the per-state column sums ARE the ledger row, so
    this variant lets the fused pass feed ``accrue_energy`` directly. Same
    one-read-per-row structure as :func:`_event_kernel`.
    """
    state = state_ref[...]
    until = until_ref[...]
    t = t_ref[...]  # (bE, 1)

    # --- per-state histogram columns: sums[e, s] = n_s(e) * power[s] ---
    cols = [
        jnp.sum(
            jnp.where(state == s, power_ref[0, s], 0.0),
            axis=1, keepdims=True,
        )
        for s in range(N_STATES)
    ]
    zero = jnp.zeros_like(cols[0])
    draw_ref[...] = jnp.concatenate(cols + [zero] * (8 - N_STATES), axis=1)

    # --- fused masked min: next strictly-future transition completion ---
    switching = jnp.logical_or(state == SWITCHING_ON, state == SWITCHING_OFF)
    future = until > t  # (bE, N) broadcast over nodes
    masked = jnp.where(
        jnp.logical_and(switching, future), until, jnp.int32(INF_TIME)
    )
    next_ref[...] = jnp.min(masked, axis=1, keepdims=True)


def _event_occ_kernel(
    state_ref,  # (bE, N) i32
    until_ref,  # (bE, N) i32
    t_ref,  # (bE, 1) i32
    gid_ref,  # (1, N) i32 node-group index
    occ_ref,  # (bE, G*8) f32 per-(group, state) node counts
    next_ref,  # (bE, 1) i32
):
    """Grouped-ledger variant: per-(group, state) occupancy counts.

    The grouped-tables engine path (core/SEMANTICS.md §Group-indexed
    tables) accrues energy as the contraction ``occ[G, 5] · power[G, 5]``,
    so the fused pass emits the raw occupancy histogram instead of power
    sums — the watts contraction (which is mode-dependent under DVFS)
    stays in the engine. Group and state are fused into one comparison
    key ``gid * 8 + state`` so each (g, s) cell costs one compare + one
    row sum; padding columns carry ``gid=0, state=PAD_STATE`` and land in
    cell (0, 7), which the wrapper slices off. Same one-read-per-row
    structure and masked next-transition min as :func:`_event_kernel`.
    """
    state = state_ref[...]
    until = until_ref[...]
    t = t_ref[...]  # (bE, 1)
    comb = gid_ref[...] * 8 + state  # (bE, N) via broadcast

    n_cells = occ_ref.shape[1]  # G*8, static
    cols = [
        jnp.sum(
            jnp.where(comb == c, 1.0, 0.0).astype(jnp.float32),
            axis=1, keepdims=True,
        )
        for c in range(n_cells)
    ]
    occ_ref[...] = jnp.concatenate(cols, axis=1)

    # --- fused masked min: next strictly-future transition completion ---
    switching = jnp.logical_or(state == SWITCHING_ON, state == SWITCHING_OFF)
    future = until > t  # (bE, N) broadcast over nodes
    masked = jnp.where(
        jnp.logical_and(switching, future), until, jnp.int32(INF_TIME)
    )
    next_ref[...] = jnp.min(masked, axis=1, keepdims=True)


def _pad_inputs(node_state, node_until, t, power, block_e):
    """Pad (E, N) operands to the kernel's tile grid; PAD_STATE rows/cols
    have zero histogram weight and until=INF (masked out of the min)."""
    e, n = node_state.shape
    n_pad = pl.cdiv(n, LANES) * LANES
    e_pad = pl.cdiv(e, block_e) * block_e
    if n_pad != n or e_pad != e:
        node_state = jnp.pad(
            node_state, ((0, e_pad - e), (0, n_pad - n)),
            constant_values=PAD_STATE,
        )
        node_until = jnp.pad(
            node_until, ((0, e_pad - e), (0, n_pad - n)),
            constant_values=int(INF_TIME),
        )
    t2 = jnp.pad(t[:, None], ((0, e_pad - e), (0, 0)))
    power8 = jnp.zeros((1, 8), jnp.float32).at[0, :N_STATES].set(power)
    return node_state, node_until, t2, power8, e_pad, n_pad


def event_fuse(
    node_state: jax.Array,  # [E, N] i32
    node_until: jax.Array,  # [E, N] i32
    t: jax.Array,  # [E] i32
    power: jax.Array,  # [5] f32
    *,
    block_e: int = 8,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused (power_draw [E], next_transition [E]) over vmapped envs."""
    e, n = node_state.shape
    node_state, node_until, t2, power8, e_pad, n_pad = _pad_inputs(
        node_state, node_until, t, power, block_e
    )
    grid = (e_pad // block_e,)
    draw, nxt = pl.pallas_call(
        _event_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_e, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_e, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_e, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_e, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((e_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((e_pad, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(node_state, node_until, t2, power8)
    return draw[:e, 0], nxt[:e, 0]


def event_fuse_occ(
    node_state: jax.Array,  # [E, N] i32
    node_until: jax.Array,  # [E, N] i32
    t: jax.Array,  # [E] i32
    group_id: jax.Array,  # [N] i32
    n_groups: int,
    *,
    block_e: int = 8,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused (occupancy [E, G, 8] f32, next_transition [E]) — grouped path.

    Live states occupy columns 0..4 of each group row; columns 5..7 are
    zero (PAD_STATE contamination from lane padding lands in cell
    ``(0, 7)`` and is zeroed below, matching the jnp reference).
    """
    e, n = node_state.shape
    n_pad = pl.cdiv(n, LANES) * LANES
    e_pad = pl.cdiv(e, block_e) * block_e
    if n_pad != n or e_pad != e:
        node_state = jnp.pad(
            node_state, ((0, e_pad - e), (0, n_pad - n)),
            constant_values=PAD_STATE,
        )
        node_until = jnp.pad(
            node_until, ((0, e_pad - e), (0, n_pad - n)),
            constant_values=int(INF_TIME),
        )
    t2 = jnp.pad(t[:, None], ((0, e_pad - e), (0, 0)))
    gid2 = jnp.pad(group_id[None, :], ((0, 0), (0, n_pad - n)))
    grid = (e_pad // block_e,)
    occ, nxt = pl.pallas_call(
        _event_occ_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_e, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_e, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, n_pad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_e, n_groups * 8), lambda i: (i, 0)),
            pl.BlockSpec((block_e, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((e_pad, n_groups * 8), jnp.float32),
            jax.ShapeDtypeStruct((e_pad, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(node_state, node_until, t2, gid2)
    occ = occ[:e].reshape(e, n_groups, 8)
    if n_pad != n:  # pad lanes counted into the dead cell (0, PAD_STATE)
        occ = occ.at[:, 0, PAD_STATE].set(0.0)
    return occ, nxt[:e, 0]


def event_fuse_ledger(
    node_state: jax.Array,  # [E, N] i32
    node_until: jax.Array,  # [E, N] i32
    t: jax.Array,  # [E] i32
    power: jax.Array,  # [5] f32
    *,
    block_e: int = 8,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused (per-state power sums [E, 8], next_transition [E])."""
    e, n = node_state.shape
    node_state, node_until, t2, power8, e_pad, n_pad = _pad_inputs(
        node_state, node_until, t, power, block_e
    )
    grid = (e_pad // block_e,)
    draw, nxt = pl.pallas_call(
        _event_ledger_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_e, n_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_e, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_e, 8), lambda i: (i, 0)),
            pl.BlockSpec((block_e, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((e_pad, 8), jnp.float32),
            jax.ShapeDtypeStruct((e_pad, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(node_state, node_until, t2, power8)
    return draw[:e], nxt[:e, 0]
