"""Pallas-TPU flash attention (forward) with GQA and causal masking.

Grid ``(B, H, Sq/bq, Sk/bk)`` with the KV dimension innermost and
``arbitrary`` (sequential) semantics; the online-softmax running state
(acc, m, l) lives in VMEM scratch and is carried across KV steps. Block
shapes are explicit BlockSpecs:

    q   (1, 1, bq, hd)   indexed (b, h, qi)          — revisited per kv step
    k/v (1, 1, bk, hd)   indexed (b, h // n_rep, ki) — GQA: query heads in the
                                                        same group share a KV
                                                        block, no materialized
                                                        repeat_kv
    out (1, 1, bq, hd)   written at the last kv step

VMEM working set per core = bq·hd (q) + 2·bk·hd (kv) + bq·hd (acc)
+ 2·bq·128 (m, l) floats — with bq=bk=128, hd=128 that is ~200 KiB, far
under the ~16 MiB v5e VMEM budget, leaving room for Mosaic's double
buffering of the kv stream. MXU alignment: bq/bk multiples of 128; hd is
the lane dim (128-aligned for the assigned archs' 128-dim heads; 64/80-dim
heads pad lanes, noted in DESIGN.md).

Causal skipping: KV blocks strictly above the diagonal are skipped via
``pl.when`` (no FLOPs, no VMEM writes), halving work for causal attention.

Numerics match ``ref.flash_attention_reference`` (fp32 accumulate,
exp-rescaled online softmax) to ~1e-6 in f32 / ~2e-2 in bf16.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _flash_kernel(
    q_ref,  # (1, 1, bq, hd)
    k_ref,  # (1, 1, bk, hd)
    v_ref,  # (1, 1, bk, hd)
    o_ref,  # (1, 1, bq, hd)
    acc_ref,  # (bq, hd) f32 scratch
    m_ref,  # (bq, LANES) f32 scratch
    l_ref,  # (bq, LANES) f32 scratch
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    kv_steps: int,
    kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip kv blocks strictly above the diagonal
    q_lo = qi * block_q
    k_lo = ki * block_k
    should_run = jnp.logical_or(
        jnp.logical_not(causal), k_lo <= q_lo + block_q - 1
    )

    @pl.when(should_run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # (bq, bk)

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_len  # tail padding
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        p = jnp.exp(s - m_new)  # (bq, bk)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, Sq, H, hd]
    k: jax.Array,  # [B, Sk, KH, hd]
    v: jax.Array,  # [B, Sk, KH, hd]
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention forward. Returns [B, Sq, H, hd]."""
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    assert h % kh == 0, "query heads must be a multiple of kv heads"
    n_rep = h // kh
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0, (sq, block_q)
    kv_steps = pl.cdiv(sk, block_k)
    sk_pad = kv_steps * block_k

    # [B, H, S, hd] layout: heads become grid dims, S x hd are the VMEM tiles
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if sk_pad != sk:
        pad = ((0, 0), (0, 0), (0, sk_pad - sk), (0, 0))
        kt = jnp.pad(kt, pad)
        vt = jnp.pad(vt, pad)

    grid = (b, h, sq // block_q, kv_steps)
    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        kv_steps=kv_steps,
        kv_len=sk,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b_, h_, qi, ki, n_rep=n_rep: (b_, h_ // n_rep, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, hd),
                lambda b_, h_, qi, ki, n_rep=n_rep: (b_, h_ // n_rep, ki, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)  # [B, Sq, H, hd]
