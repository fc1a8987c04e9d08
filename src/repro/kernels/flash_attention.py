"""Blocked causal flash attention (GQA) — Pallas TPU kernel.

The serving/training hot spot of every assigned LM architecture.  Online-
softmax streaming over key blocks (FlashAttention-2 schedule adapted to the
TPU grid model):

  grid = (B·Hq, Sq/block_q, Skv/block_k)   — k innermost, sequential, so the
  running (m, l, acc) state lives in VMEM scratch and is revisited across
  the k dimension; the final normalized tile is written once at the last
  k step.  Block shapes are MXU-aligned (block_q, block_k multiples of 128
  on real hardware; the tests sweep smaller interpret-mode tiles).

TPU adaptation notes (vs the CUDA formulation):
  * no warp-level reductions — rowmax/rowsum are VPU ops over the (8,128)
    register tiles, which XLA/Mosaic handles; we keep reductions on the
    last axis so they stay in-lane.
  * masking uses a large *finite* negative (−1e30) instead of −inf: −inf
    arithmetic (−inf − −inf) produces NaNs in f32 on both MXU paths and
    interpret mode; with the causal structure every row has ≥1 valid key,
    so the finite mask is exact after normalization.
  * GQA is expressed in the index_map (query head → kv head), so no
    repeated KV materialization in HBM: the same kv block is streamed to
    all heads of a group.
  * the causal upper-triangle blocks are skipped with ``pl.when`` — work
    saving visible in the cost model, not just latency.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  sm_scale: float, causal: bool, block_q: int, block_k: int,
                  seq_off: int):
    """One (q-block, k-block) step.  Refs: q (block_q, D), k/v (block_k, D),
    o (block_q, D); scratch m/l (block_q, 1) and acc (block_q, D) in VMEM."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal block pruning: skip blocks strictly above the diagonal
    q_last = (qi + 1) * block_q - 1 + seq_off
    k_first = ki * block_k
    run = (not causal) or (k_first <= q_last)

    @pl.when(run)
    def _body():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + seq_off
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]                       # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)           # finite: NEG_INF - NEG_INF = 0
        p = jnp.exp(s - m_new)                    # (bq, bk)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).  Returns (B, Hq, Sq, D).

    Sq may be shorter than Skv (chunked prefill): queries are the last Sq
    positions of the context.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = float(sm_scale) if sm_scale is not None else float(1.0 / np.sqrt(D))
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    seq_off = Skv - Sq

    qr = q.reshape(B * Hq, Sq, D)
    kr = k.reshape(B * Hkv, Skv, D)
    vr = v.reshape(B * Hkv, Skv, D)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        b = bh // Hq
        h = bh % Hq
        return (b * Hkv + h // group, ki, 0)

    kernel = functools.partial(
        _flash_kernel, sm_scale=scale, causal=causal,
        block_q=bq, block_k=bk, seq_off=seq_off)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, Sq // bq, Skv // bk),
        in_specs=[
            pl.BlockSpec((None, bq, D), q_map),
            pl.BlockSpec((None, bk, D), kv_map),
            pl.BlockSpec((None, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((None, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, Hq, Sq, D)
