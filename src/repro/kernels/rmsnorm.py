"""Fused RMSNorm — Pallas kernel.

Memory-bound epilogue op: unfused, XLA reads x twice (square-mean, then
normalize) and writes the normalized intermediate before the scale
multiply.  Fusing keeps the (block_t, D) tile resident in VMEM for the
whole read→reduce→scale pipeline: one HBM read + one HBM write per
element, i.e. the op runs at streaming bandwidth.

Also covers OLMo's *non-parametric* LayerNorm (scale=None → pure
normalization, no learned affine).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    y = y * s_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _rmsnorm_kernel_noscale(x_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("eps", "block_t", "interpret"))
def rmsnorm(x: jax.Array, scale: jax.Array | None = None,
            eps: float = 1e-6, block_t: int = 256, *,
            interpret: bool) -> jax.Array:
    """x: (..., D); scale: (D,) or None (non-parametric)."""
    orig_shape = x.shape
    D = orig_shape[-1]
    xr = x.reshape(-1, D)
    T = xr.shape[0]
    bt = min(block_t, T)
    if T % bt != 0:  # pad rows to a block multiple; rows are independent
        pad = bt - T % bt
        xr = jnp.concatenate([xr, jnp.zeros((pad, D), xr.dtype)], axis=0)
    Tp = xr.shape[0]
    if scale is not None:
        out = pl.pallas_call(
            functools.partial(_rmsnorm_kernel, eps=eps),
            grid=(Tp // bt,),
            in_specs=[pl.BlockSpec((bt, D), lambda t: (t, 0)),
                      pl.BlockSpec((1, D), lambda t: (0, 0))],
            out_specs=pl.BlockSpec((bt, D), lambda t: (t, 0)),
            out_shape=jax.ShapeDtypeStruct((Tp, D), x.dtype),
            interpret=interpret,
        )(xr, scale.reshape(1, D))
    else:
        out = pl.pallas_call(
            functools.partial(_rmsnorm_kernel_noscale, eps=eps),
            grid=(Tp // bt,),
            in_specs=[pl.BlockSpec((bt, D), lambda t: (t, 0))],
            out_specs=pl.BlockSpec((bt, D), lambda t: (t, 0)),
            out_shape=jax.ShapeDtypeStruct((Tp, D), x.dtype),
            interpret=interpret,
        )(xr)
    return out[:T].reshape(orig_shape)
