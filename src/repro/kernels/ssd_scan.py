"""Pallas-TPU chunked SSD/GLA scan — the Mamba-2 / mLSTM training core.

Implements the gated-linear-attention recurrence

    h_t = exp(g_t) · h_{t-1} + k_t ⊗ v_t
    y_t = q_t · h_t

in the chunk-parallel "state-space duality" form: within a chunk the output
is a masked decay-weighted (Q·Kᵀ)·V product (two MXU matmuls), and only the
O(S/chunk) inter-chunk state pass is sequential. Grid is
``(B, H, S/chunk)`` with the chunk dimension innermost and ``arbitrary``
semantics; the running state ``h ∈ [dk, dv]`` (f32) lives in VMEM scratch
and is carried across chunk steps — the sequential dependency never leaves
the core.

Block shapes (per grid step):

    q/k (1, 1, C, dk), v (1, 1, C, dv), g (1, 1, C, 1)   C = chunk
    y   (1, 1, C, dv)                                      written per step
    hT  (1, 1, dk, dv)                                     final state, written
                                                            at the last step

VMEM working set: C·(2dk+2dv) + C² (decay matrix) + dk·dv floats — with
C=128, dk=dv=128 that's ~190 KiB. All decays g ≤ 0, so every exponential in
the chunk program is ≤ 1 and no max-stabilizer bookkeeping is needed
(numerics note in ssm.py).

Oracle: ``ref.gla_reference`` (sequential scan) and the XLA twin
``repro.models.ssm.chunked_gla``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    q_ref,  # (1, 1, C, dk)
    k_ref,  # (1, 1, C, dk)
    v_ref,  # (1, 1, C, dv)
    g_ref,  # (1, 1, C, 1)
    y_ref,  # (1, 1, C, dv)
    hT_ref,  # (1, 1, dk, dv)
    h_ref,  # (dk, dv) f32 scratch — inter-chunk state
    *,
    chunk: int,
    n_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (C, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    g = g_ref[0, 0].astype(jnp.float32)  # (C, 1) log-decay per step

    bcum = jnp.cumsum(g, axis=0)  # (C, 1) inclusive decay from chunk start
    b_end = bcum[chunk - 1 :, :]  # (1, 1) total chunk decay

    # intra-chunk: y[t] = sum_{s<=t} exp(b_t - b_s) (q_t . k_s) v_s
    diff = bcum - bcum.reshape(1, chunk)  # (C, C): b_t - b_s
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(t_idx >= s_idx, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (C, C)
    y_intra = jax.lax.dot_general(
        scores * decay, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (C, dv)

    # inter-chunk: y[t] += exp(b_t) q_t . h_in
    h_in = h_ref[...]
    y_inter = jax.lax.dot_general(
        q * jnp.exp(bcum), h_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h = exp(b_end) h_in + sum_s exp(b_end - b_s) k_s v_s
    k_scaled = k * jnp.exp(b_end - bcum)  # (C, dk)
    h_new = jnp.exp(b_end[0, 0]) * h_in + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h_ref[...] = h_new

    @pl.when(ci == n_chunks - 1)
    def _final():
        hT_ref[0, 0] = h_new


def ssd_scan(
    q: jax.Array,  # [B, S, H, dk]
    k: jax.Array,  # [B, S, H, dk]
    v: jax.Array,  # [B, S, H, dv]
    g: jax.Array,  # [B, S, H] log-decay (<= 0)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Chunk-parallel GLA scan. Returns (y [B,S,H,dv], h_final [B,H,dk,dv])."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk

    qt = jnp.moveaxis(q, 2, 1)  # [B, H, S, dk]
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    gt = jnp.moveaxis(g, 2, 1)[..., None]  # [B, H, S, 1]

    grid = (b, h, n_chunks)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    y, hT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, ci: (b_, h_, ci, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, ci: (b_, h_, ci, 0)),
            pl.BlockSpec((1, 1, chunk, dv), lambda b_, h_, ci: (b_, h_, ci, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, ci: (b_, h_, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, dv), lambda b_, h_, ci: (b_, h_, ci, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b_, h_, ci: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dv), v.dtype),
            jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt, gt)
    return jnp.moveaxis(y, 1, 2), hT
