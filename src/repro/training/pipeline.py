"""GPipe-style pipeline parallelism over a mesh axis via shard_map +
collective_permute.

The model's scan-over-layers stack splits into ``n_stages`` contiguous
stages; each pipeline rank holds ONE stage's parameters (sharded over the
``pipe`` mesh axis) and microbatched activations flow rank->rank+1 with
``jax.lax.ppermute``. The schedule is the classic GPipe fill-drain loop of
``n_micro + n_stages - 1`` ticks; bubble fraction = (S-1)/(M+S-1), so
n_micro >= 4 x n_stages keeps it under ~20%.

This is OFF by default (DP over pods wins at 2 pods — the gradient
all-reduce overlaps with accumulation, while a 2-stage pipeline adds a
bubble and cross-pod activation traffic *per microbatch*; see EXPERIMENTS.md
§Perf for the measured trade). It exists so the same launcher scales to
meshes where the model axis alone cannot hold the weights — and it is
dry-run-verified on the (pod, data, model) production mesh in
tests/test_pipeline.py.

Activation shapes must be rank-invariant (same [mb, S, D] at every stage),
which holds for every assigned arch's homogeneous trunk.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


def pipeline_forward(
    stage_fn: Callable[[PyTree, jax.Array], jax.Array],
    stage_params: PyTree,  # leaves with leading axis == n_stages (sharded over pipe axis)
    x: jax.Array,  # [n_micro, mb, ...] microbatched input (replicated)
    *,
    mesh: Mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run x through the stage pipeline. Returns [n_micro, mb, ...] outputs.

    Inside shard_map each rank sees stage_params[1, ...] (its own stage) and
    the full microbatch stream. Rank r processes microbatch m at tick
    t = m + r; activations hop via ppermute; outputs are collected on the
    last rank then broadcast (all ranks return identical outputs).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    n_ticks = n_micro + n_stages - 1

    def per_rank(params, xs):
        # params: [1, ...] this rank's stage; xs: [n_micro, mb, ...] (full)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        rank = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros_like(xs)  # collected outputs (last rank)
        carry = jnp.zeros(mb_shape, xs.dtype)  # activation entering this rank

        def tick(t, state):
            carry, buf = state
            # rank 0 ingests microbatch t; others use the permuted carry
            x_in = jnp.where(
                rank == 0,
                jax.lax.dynamic_index_in_dim(
                    xs, jnp.minimum(t, n_micro - 1), 0, keepdims=False
                ),
                carry,
            )
            my_m = t - rank  # microbatch index this rank works on at tick t
            active = jnp.logical_and(my_m >= 0, my_m < n_micro)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, x_in)
            # last rank collects finished microbatches
            buf = jnp.where(
                jnp.logical_and(rank == n_stages - 1, active),
                jax.lax.dynamic_update_index_in_dim(buf, y, jnp.maximum(my_m, 0), 0),
                buf,
            )
            # hop to the next rank (ring; the wrap-around value is ignored)
            carry = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return carry, buf

        _, buf = jax.lax.fori_loop(0, n_ticks, tick, (carry, buf))
        # broadcast results from the last rank to all ranks
        out = jax.lax.ppermute(
            buf, axis, [((n_stages - 1 + i) % n_stages, i) for i in range(n_stages)]
        )
        # ranks other than the one fed by last now hold garbage; an
        # all-gather-max settles it (outputs are identical where valid)
        out = jnp.where(rank == 0, out, jnp.zeros_like(out))
        out = jax.lax.psum(out, axis)
        return out

    spec_p = jax.tree_util.tree_map(
        lambda _: P(axis), stage_params
    )
    fn = jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(spec_p, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)


def split_stages(stacked_params: PyTree, n_stages: int) -> PyTree:
    """[L, ...] layer-stacked params -> [n_stages, L/n_stages, ...]."""

    def leaf(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape((n_stages, l // n_stages) + a.shape[1:])

    return jax.tree_util.tree_map(leaf, stacked_params)


def make_stage_fn(
    block_apply: Callable[[PyTree, jax.Array], jax.Array],
) -> Callable[[PyTree, jax.Array], jax.Array]:
    """Wrap a single-layer apply into a scan over the stage's layer stack."""

    def stage_fn(stage_params, x):
        def body(xx, lp):
            return block_apply(lp, xx), None

        y, _ = jax.lax.scan(body, x, stage_params)
        return y

    return stage_fn
