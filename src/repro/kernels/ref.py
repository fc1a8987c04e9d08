"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth its kernel is validated
against (tests/test_kernels.py sweeps shapes/dtypes and asserts
allclose in interpret mode).  ops.py dispatches to them off-TPU; on a
TPU only the kernels run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30  # finite mask value — see flash_attention.py for why


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, sm_scale: float | None = None) -> jax.Array:
    """Full softmax attention with GQA.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D); Hq % Hkv == 0.
    Returns (B, Hq, Sq, D) in q.dtype (accumulation in f32).
    """
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    Skv = k.shape[2]
    group = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        # queries are the *last* Sq positions of the Skv context
        q_pos = jnp.arange(Sq) + (Skv - Sq)
        k_pos = jnp.arange(Skv)
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return o.astype(q.dtype)


def decode_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         lengths: jax.Array, *,
                         sm_scale: float | None = None) -> jax.Array:
    """Single-token decode attention against a (padded) KV cache.

    q: (B, Hq, D); caches: (B, Hkv, S, D); lengths: (B,) valid prefix sizes.
    Returns (B, Hq, D).

    GQA-aware: contracts the query-head group against the *un-repeated*
    cache (repeating a 32k-token cache group-fold in f32 was the dominant
    decode collective: GSPMD all-gathered the materialized copy per layer
    — §Perf hillclimb B)."""
    B, Hq, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(B, Hkv, group, D)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    valid = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p.astype(q.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, D).astype(q.dtype)


def chunked_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                          causal: bool = True,
                          sm_scale: float | None = None,
                          chunk: int = 1024) -> jax.Array:
    """Flash-style attention in pure jnp: ``lax.scan`` over KV chunks with
    a running (m, l, acc) online softmax — the XLA-HLO twin of the Pallas
    kernel, used on the compiled (dry-run / CPU SPMD) path so peak
    activation memory is O(Sq·chunk) instead of O(Sq·Skv).

    GQA-aware (§Perf hillclimb C): queries fold to (B, Hkv, G, Sq, D) and
    contract against the *un-repeated* KV chunk — the previous
    ``jnp.repeat(kv, group)`` materialized group-copies of every chunk in
    f32 (measured 1.4 TB/step of traffic + a same-sized all-gather on
    kimi).  Scores/probabilities accumulate in f32 via
    ``preferred_element_type`` with operands kept in the input dtype, so
    bf16 models stream bf16 bytes through the MXU.

    Matches attention_ref to float tolerance (tests/test_kernels.py)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    c = min(chunk, Skv)
    assert Skv % c == 0, (Skv, c)
    n_chunks = Skv // c
    seq_off = Skv - Sq
    f32 = jnp.float32

    qg = (q * jnp.asarray(scale, q.dtype)).reshape(B, Hkv, group, Sq, D)
    kc = k.reshape(B, Hkv, n_chunks, c, D)
    vc = v.reshape(B, Hkv, n_chunks, c, D)
    q_pos = jnp.arange(Sq, dtype=jnp.int32) + seq_off

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, ci = xs                      # (B, Hkv, c, D) ×2, scalar
        s = jnp.einsum("bkgqd,bkcd->bkgqc", qg, kb,
                       preferred_element_type=f32)
        if causal:
            k_pos = ci * c + jnp.arange(c, dtype=jnp.int32)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqc,bkcd->bkgqd", p.astype(q.dtype), vb,
            preferred_element_type=f32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, group, Sq), NEG_INF, f32)
    l0 = jnp.zeros((B, Hkv, group, Sq), f32)
    a0 = jnp.zeros((B, Hkv, group, Sq, D), f32)
    xs = (kc.transpose(2, 0, 1, 3, 4), vc.transpose(2, 0, 1, 3, 4),
          jnp.arange(n_chunks, dtype=jnp.int32))
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), xs)
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# MoE router
# ---------------------------------------------------------------------------
def moe_router_ref(logits: jax.Array, k: int, *,
                   renormalize: bool = True) -> tuple[jax.Array, jax.Array]:
    """Fused softmax + top-k gate.

    logits: (T, E).  Returns (weights (T, k) f32, indices (T, k) i32).
    Weights are the softmax probabilities of the selected experts,
    renormalized to sum to 1 when ``renormalize``.
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx.astype(jnp.int32)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rmsnorm_ref(x: jax.Array, scale: jax.Array | None,
                eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# WikiKV storage operators (the paper's Q1/Q4 on device)
# ---------------------------------------------------------------------------
def path_lookup_ref(keys_hi: jax.Array, keys_lo: jax.Array,
                    q_hi: jax.Array, q_lo: jax.Array) -> jax.Array:
    """Batched GET over the sorted 64-bit digest table (row id or −1).
    Mirrors core.tensorstore.lookup_ref (kept independent so the kernel
    test oracle has no dependency on core)."""
    n = keys_hi.shape[0]
    lo = jnp.zeros(q_hi.shape, dtype=jnp.int32)
    hi = jnp.full(q_hi.shape, n, dtype=jnp.int32)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) // 2
        mid_c = jnp.clip(mid, 0, n - 1)
        khi = keys_hi[mid_c]
        klo = keys_lo[mid_c]
        lt = (khi < q_hi) | ((khi == q_hi) & (klo < q_lo))
        return (jnp.where(lt, mid + 1, lo), jnp.where(lt, hi, mid))

    steps = int(np.ceil(np.log2(max(int(n), 2)))) + 1
    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    idx = jnp.clip(lo, 0, n - 1)
    hit = (keys_hi[idx] == q_hi) & (keys_lo[idx] == q_lo)
    return jnp.where(hit, idx, -1)


def path_lookup_pinned_ref(keys_hi: jax.Array, keys_lo: jax.Array,
                           q_hi: jax.Array, q_lo: jax.Array,
                           pin_hi: jax.Array, pin_lo: jax.Array,
                           pin_pos: jax.Array) -> jax.Array:
    """Oracle for the pinned-probe kernel path: a query matching the
    pinned sub-table resolves to its staged sorted-table position; the
    rest fall through to the binary search.  When the staging is
    consistent (pin_pos[j] == position of (pin_hi, pin_lo)[j] in the
    sorted table), this equals plain ``path_lookup_ref``."""
    base = path_lookup_ref(keys_hi, keys_lo, q_hi, q_lo)
    eq = (pin_hi[None, :] == q_hi[:, None]) & (pin_lo[None, :] == q_lo[:, None])
    hit = jnp.any(eq, axis=1)
    pos = jnp.sum(jnp.where(eq, pin_pos[None, :], 0), axis=1).astype(jnp.int32)
    return jnp.where(hit, pos, base)


def prefix_search_ref(tokens: jax.Array, prefix: jax.Array,
                      prefix_len: jax.Array) -> jax.Array:
    """Bitmap of rows whose packed path starts with ``prefix`` (segment-
    aware).  tokens: (N, L) uint8; prefix: (L,) uint8; prefix_len: int32."""
    L = tokens.shape[1]
    pos = jnp.arange(L, dtype=jnp.int32)
    within = pos < prefix_len
    eq = (tokens == prefix[None, :]) | ~within[None, :]
    starts = jnp.all(eq, axis=1)
    nxt = tokens[:, jnp.minimum(prefix_len, L - 1)]
    last = prefix[jnp.maximum(prefix_len - 1, 0)]
    boundary_ok = (last == ord("/")) | (nxt == 0) | (nxt == ord("/"))
    exact_fits = prefix_len < L
    return starts & jnp.where(exact_fits, boundary_ok, True)
