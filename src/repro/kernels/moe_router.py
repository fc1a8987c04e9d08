"""Fused MoE router: softmax + top-k gate — Pallas kernel.

Hot on dbrx (16e top-4), kimi-k2 (384e top-8) and jamba (16e top-2):
the gate runs on *every token* of every MoE layer, and the unfused
softmax→top_k→renorm chain materializes (T, E) probabilities three times
in HBM.  This kernel keeps the (block_t, E) tile in VMEM and performs the
iterative arg-max selection in registers, emitting only the (block_t, k)
weights/indices.

TPU adaptation: GPU implementations use warp ballot/shuffle for the
top-k; here selection is k rounds of a full-width VPU max + one-hot
masking — O(k·E) lanework, branch-free, no data-dependent shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _router_kernel(logits_ref, w_ref, idx_ref, *, k: int, renormalize: bool):
    """Refs: logits (block_t, E) → w (block_t, k) f32, idx (block_t, k) i32."""
    x = logits_ref[...].astype(jnp.float32)
    bt, E = x.shape
    m = x.max(axis=1, keepdims=True)
    p = jnp.exp(x - m)
    p = p / p.sum(axis=1, keepdims=True)

    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, E), 1)
    remaining = p
    ws = []
    ids = []
    for _ in range(k):  # k is small and static — unrolled selection rounds
        w = remaining.max(axis=1)                        # (bt,)
        # first-match index (ties broken toward lower expert id, matching
        # jax.lax.top_k's stable ordering)
        is_max = remaining == w[:, None]
        idx = jnp.min(jnp.where(is_max, cols, E), axis=1).astype(jnp.int32)
        ws.append(w)
        ids.append(idx)
        remaining = jnp.where(cols == idx[:, None], NEG_INF, remaining)
    w_out = jnp.stack(ws, axis=1)
    if renormalize:
        w_out = w_out / w_out.sum(axis=1, keepdims=True)
    w_ref[...] = w_out
    idx_ref[...] = jnp.stack(ids, axis=1)


@functools.partial(
    jax.jit, static_argnames=("k", "renormalize", "block_t", "interpret"))
def moe_router(logits: jax.Array, k: int, *, renormalize: bool = True,
               block_t: int = 256, interpret: bool
               ) -> tuple[jax.Array, jax.Array]:
    """logits: (T, E) → (weights (T, k) f32, indices (T, k) i32)."""
    T, E = logits.shape
    bt = min(block_t, T)
    assert T % bt == 0, (T, bt)
    kernel = functools.partial(_router_kernel, k=k, renormalize=renormalize)
    w, idx = pl.pallas_call(
        kernel,
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, E), lambda t: (t, 0))],
        out_specs=[
            pl.BlockSpec((bt, k), lambda t: (t, 0)),
            pl.BlockSpec((bt, k), lambda t: (t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, k), jnp.float32),
            jax.ShapeDtypeStruct((T, k), jnp.int32),
        ],
        interpret=interpret,
    )(logits)
    return w, idx
