"""Mixture-of-Experts FFN with expert parallelism.

Routing runs through the fused Pallas gate (kernels/moe_router); expert
compute is a *capacity-based batched dispatch*:

  sort assignments by expert → scatter token ids into an (E_loc, C_e)
  index buffer (capacity C_e per expert, GShard discipline; overflow
  drops) → gather tokens to (E_loc, C_e, D) → one batched einsum per
  projection → scatter-add combine weighted by the gate.

FLOPs are exact up to the capacity factor (E_loc·C_e·D·F ≈ top_k·T·D·F·cf)
— no one-hot dispatch einsums, and no ``lax.ragged_dot`` (whose XLA
expansion materializes dense per-group masks: measured 26 GiB × 24
buffers on kimi's 24-expert shard before this formulation).  The batched
einsum form is also what the TPU MXU wants: one (C_e × D × F) matmul per
expert, weight-stationary.

Two execution paths:

* ``moe_apply_local``  — single shard, all experts local (CPU smoke
  tests; also the k=top_k dense fallback).
* ``moe_apply``        — expert-parallel via shard_map: activations are
  replicated across the TP/EP axis between blocks (Megatron convention),
  experts sharded over it.  Each device keeps the assignments that land
  on *its* expert slice (local capacity-bounded selection — tokens are
  already resident, so dispatch needs **no all-to-all**), runs its local
  batched FFN, and partial outputs combine with one ``psum`` over the EP
  axis — the same single collective a dense TP FFN pays.

Shared experts (kimi-style) are a dense gated MLP added unconditionally.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..kernels import ops
from .config import ModelConfig
from .layers import FSDP, TP, _dtype, dense_init, mlp_apply, mlp_init


def moe_init(key, cfg: ModelConfig):
    assert cfg.moe is not None
    m = cfg.moe
    ks = jax.random.split(key, 5)
    D, F, E = cfg.d_model, m.d_ff_expert, m.n_experts
    params, specs = {}, {}
    params["router"], specs["router"] = dense_init(
        ks[0], D, E, cfg, (None, None), scale=0.02)
    # experts stacked on a leading E axis, sharded over the TP/EP axis
    def experts(k, d_in, d_out):
        w = (jax.random.normal(k, (E, d_in, d_out), jnp.float32)
             / np.sqrt(d_in)).astype(_dtype(cfg))
        return w, P(TP, FSDP, None)
    params["w_gate"], specs["w_gate"] = experts(ks[1], D, F)
    params["w_up"], specs["w_up"] = experts(ks[2], D, F)
    params["w_down"], specs["w_down"] = experts(ks[3], F, D)
    if m.n_shared > 0:
        sh, shs = mlp_init(ks[4], cfg, d_ff=F * m.n_shared)
        params["shared"], specs["shared"] = sh, shs
    return params, specs


def _dispatch_ffn(x, local_e, tok_flat, w_flat, n_local, cap_e,
                  w_gate, w_up, w_down):
    """Capacity dispatch + batched expert FFN + weighted combine.

    x: (T, D); local_e: (A,) local expert id per assignment (n_local ⇒
    not-mine/invalid); tok_flat/w_flat: (A,) token id / gate weight.
    Returns (T, D) f32 partial output (zeros for tokens with no local
    assignment)."""
    T, D = x.shape
    A = local_e.shape[0]
    order = jnp.argsort(local_e, stable=True)       # experts ascending,
    sorted_e = local_e[order]                       # invalid last
    sorted_tok = tok_flat[order]
    sorted_w = w_flat[order]
    sizes = jnp.bincount(local_e, length=n_local + 1)[:n_local]
    starts = jnp.concatenate(
        [jnp.zeros((1,), sizes.dtype), jnp.cumsum(sizes)[:-1]])
    pos_in_e = (jnp.arange(A, dtype=jnp.int32)
                - starts[jnp.clip(sorted_e, 0, n_local - 1)].astype(jnp.int32))
    valid = (sorted_e < n_local) & (pos_in_e < cap_e) & (pos_in_e >= 0)
    e_safe = jnp.where(valid, sorted_e, n_local)    # OOB ⇒ dropped
    p_safe = jnp.where(valid, pos_in_e, cap_e)
    buf = jnp.zeros((n_local, cap_e), jnp.int32).at[e_safe, p_safe].set(
        sorted_tok, mode="drop")
    wbuf = jnp.zeros((n_local, cap_e), jnp.float32).at[e_safe, p_safe].set(
        sorted_w, mode="drop")
    xs = x[buf]                                     # (E_loc, C_e, D)
    g = jnp.einsum("ecd,edf->ecf", xs, w_gate.astype(xs.dtype))
    u = jnp.einsum("ecd,edf->ecf", xs, w_up.astype(xs.dtype))
    h = (jax.nn.silu(g.astype(jnp.float32))
         * u.astype(jnp.float32)).astype(xs.dtype)
    ys = jnp.einsum("ecf,efd->ecd", h, w_down.astype(xs.dtype))
    contrib = ys.astype(jnp.float32) * wbuf[..., None]   # gate=0 ⇒ no-op
    out = jnp.zeros((T, D), jnp.float32)
    out = out.at[buf.reshape(-1)].add(contrib.reshape(-1, D))
    return out


def _capacity(tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    return max(int(np.ceil(tokens * top_k / max(n_experts, 1) * cf)), 4)


def moe_apply_local(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Single-shard MoE: x (T, D) → (T, D)."""
    m = cfg.moe
    T, D = x.shape
    logits = (x @ params["router"]).astype(jnp.float32)
    weights, idx = ops.moe_router(logits, m.top_k)          # (T, k)
    idx_flat = idx.reshape(-1)                              # (T·k,)
    tok_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), m.top_k)
    w_flat = weights.reshape(-1)
    cap = _capacity(T, m.top_k, m.n_experts, m.capacity_factor)
    out = _dispatch_ffn(x, idx_flat, tok_flat, w_flat, m.n_experts, cap,
                        params["w_gate"], params["w_up"], params["w_down"])
    out = out.astype(x.dtype)
    if m.n_shared > 0:
        out = out + mlp_apply(params["shared"], x)
    return out


def _moe_shard_body(x: jax.Array, router: jax.Array, w_gate, w_up, w_down,
                    *, cfg: ModelConfig, ep_shards: int, axis: str):
    """Per-device body under shard_map.

    x: (T_loc, D) — local tokens (sharded over data, replicated over
    TP/EP).  w_*: (E_loc, …) — this device's expert slice.  Every EP
    member computes the same router output for its token slice, keeps
    assignments for its own experts, and psums the partials."""
    m = cfg.moe
    T, D = x.shape
    E_loc = w_gate.shape[0]
    my = jax.lax.axis_index(axis)
    lo = my * E_loc

    logits = (x @ router).astype(jnp.float32)
    weights, idx = ops.moe_router(logits, m.top_k)
    idx_flat = idx.reshape(-1)
    tok_flat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), m.top_k)
    w_flat = weights.reshape(-1)
    mine = (idx_flat >= lo) & (idx_flat < lo + E_loc)
    local_e = jnp.where(mine, idx_flat - lo, E_loc)
    cap = _capacity(T, m.top_k, m.n_experts, m.capacity_factor)
    partial_out = _dispatch_ffn(x, local_e, tok_flat, w_flat, E_loc, cap,
                                w_gate, w_up, w_down)
    # combine in bf16: halves the EP-psum bytes (the per-layer collective)
    return jax.lax.psum(partial_out.astype(x.dtype), axis)


def moe_apply(params: dict, x: jax.Array, cfg: ModelConfig,
              mesh=None) -> jax.Array:
    """x: (B, S, D) → (B, S, D).  EP path when a mesh with a TP axis whose
    size divides n_experts is active; local path otherwise."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    m = cfg.moe
    ep = 0
    if mesh is not None and TP in mesh.axis_names:
        tp = mesh.shape[TP]
        if tp > 1 and m.n_experts % tp == 0:
            ep = tp
    if ep:
        data_axes = tuple(a for a in mesh.axis_names if a != TP)
        dp_size = int(np.prod([mesh.shape[a] for a in data_axes])) if data_axes else 1
        # decode batches too small to split stay replicated over data
        x_spec = (P(data_axes, None)
                  if (B * S) % max(dp_size, 1) == 0 and B * S >= dp_size
                  else P(None, None))
        body = partial(_moe_shard_body, cfg=cfg, ep_shards=ep, axis=TP)
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(x_spec, P(None, None),
                      P(TP, None, None), P(TP, None, None), P(TP, None, None)),
            out_specs=x_spec,
            check_vma=False,
        )(xt, params["router"], params["w_gate"], params["w_up"],
          params["w_down"])
        if m.n_shared > 0:
            out = out + mlp_apply(params["shared"], xt)
    else:
        out = moe_apply_local(params, xt, cfg)
    return out.reshape(B, S, D)
