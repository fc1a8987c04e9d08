"""Single-token decode attention against a padded KV cache — Pallas kernel.

The serving engine's inner loop (``decode_32k`` / ``long_500k`` shapes):
one new query token per sequence attends over a long cached context.
FlashDecoding-style split-KV: the kv sequence is the innermost grid
dimension, partial (m, l, acc) state accumulates in VMEM scratch, and
positions beyond the live ``length`` of each sequence are masked.

TPU adaptation: the split-KV *reduction tree* of the GPU formulation
(separate combine kernel over SM partial results) is unnecessary — the
sequential TPU grid revisits scratch across k blocks, so the combine is
fused for free.  What we keep from the paper^W GPU idea is the split of
the KV stream into VMEM-sized tiles so a 512k-token cache never has to
fit on-chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_F32 = jax.lax.Precision.HIGHEST


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, sm_scale: float, block_k: int):
    """Refs: len (B·Hkv,) int32 in SMEM (scalar prefetch); q (Hg, D) —
    the query-head group attending one kv head; k/v (block_k, D); o
    (Hg, D)."""
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]
    # skip kv blocks entirely beyond the live prefix
    @pl.when(ki * block_k < length)
    def _body():
        q = q_ref[...].astype(jnp.float32)           # (Hg, D)
        k = k_ref[...].astype(jnp.float32)           # (bk, D)
        v = v_ref[...].astype(jnp.float32)
        # float32 accuracy on the MXU: the default precision would round
        # the operands to bfloat16
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32) * sm_scale   # (Hg, bk)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "block_k", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, sm_scale: float | None = None,
                     block_k: int = 256, interpret: bool) -> jax.Array:
    """q: (B, Hq, D); k_cache/v_cache: (B, Hkv, S, D); lengths: (B,) int32.

    Grid = (B·Hkv, S/block_k): one program row per (sequence, kv head),
    carrying the whole query-head *group* (Hq/Hkv rows) so the MXU matmul
    has a real M dimension even at batch-of-one decode.
    """
    B, Hq, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = float(sm_scale) if sm_scale is not None else float(1.0 / np.sqrt(D))
    bk = min(block_k, S)
    assert S % bk == 0

    # (B, Hkv, group, D): group-major query layout per kv head
    qr = q.reshape(B, Hkv, group, D).reshape(B * Hkv, group, D)
    kr = k_cache.reshape(B * Hkv, S, D)
    vr = v_cache.reshape(B * Hkv, S, D)
    lens = jnp.repeat(lengths.astype(jnp.int32), Hkv)

    def q_map(bh, ki, lens_ref):
        return (bh, 0, 0)

    def kv_map(bh, ki, lens_ref):
        return (bh, ki, 0)

    kernel = functools.partial(_decode_kernel, sm_scale=scale, block_k=bk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hkv, S // bk),
        in_specs=[
            pl.BlockSpec((None, group, D), q_map),
            pl.BlockSpec((None, bk, D), kv_map),
            pl.BlockSpec((None, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((None, group, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hkv, group, D), q.dtype),
        interpret=interpret,
    )(lens, qr, kr, vr)
    return out.reshape(B, Hkv, group, D).reshape(B, Hq, D)
