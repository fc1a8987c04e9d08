"""Batched prefix search (the paper's Q4/SEARCH, TPU-native) — Pallas kernel.

SEARCH(p) over the packed path-token matrix: a pure streaming op — each
grid step pulls one tile of ``block_n`` paths into VMEM, compares it
against every pending prefix while resident, applies the segment-
boundary rule ("/a" must not match "/ab"), and emits one match row per
prefix.

Layout: the wrapper packs each path's L bytes into L/4 little-endian
int32 words and lays the words out path-minor, (L/4, N/128, 128), so one
word of 1024 consecutive paths is a dense (8, 128) vreg tile and the
path axis — never the small prefix batch — is the lane axis.  Per prefix
the kernel XORs each word against the prefix word (an SMEM scalar),
masks the bytes beyond the prefix length and ORs into a mismatch
accumulator: 4 bytes compared per lane op, with only the ⌈len/4⌉ words
the prefix covers visited.  The boundary byte is one dynamic word load
plus a shift.  Output rows are int8 (Q, N) — lane-dense in N.

This is the bandwidth-roofline member of the kernel set: for N = 10⁷
paths × 96 B ≈ 1 GB, one pass is ~1.2 ms at 819 GB/s, amortized across
every query in the routing batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: words-per-prefix scalar fields after the L/4 prefix words and masks
_N_META = 4      # n_words, boundary word, boundary shift, boundary check


def _prefix_kernel(meta_ref, tok_ref, out_ref, *, n_words: int):
    """Refs: meta (Qp·(2W+4),) int32 SMEM (scalar prefetch) — per prefix W
    words, W byte masks, then ``_N_META`` scalars; tokens (W, bs, 128)
    int32 path words; out (Qp, bs, 128) int8."""
    stride = 2 * n_words + _N_META
    n_q = out_ref.shape[0]

    def one_prefix(q, carry):
        base = q * stride
        meta = base + 2 * n_words

        def word(w, acc):
            x = tok_ref[w] ^ meta_ref[base + w]
            return acc | (x & meta_ref[base + n_words + w])

        zero = jnp.zeros(out_ref.shape[1:], jnp.int32)
        diff = jax.lax.fori_loop(0, meta_ref[meta], word, zero)
        nxt = jax.lax.shift_right_logical(
            tok_ref[meta_ref[meta + 1]], meta_ref[meta + 2]) & 0xFF
        ok = (nxt == 0) | (nxt == ord("/")) | (meta_ref[meta + 3] == 0)
        out_ref[q] = ((diff == 0) & ok).astype(jnp.int8)
        return carry

    jax.lax.fori_loop(0, n_q, one_prefix, 0)


def _pack_words(b: jax.Array) -> jax.Array:
    """(..., L) uint8 with L % 4 == 0 → (..., L/4) int32, little-endian."""
    w = b.astype(jnp.uint32).reshape(*b.shape[:-1], b.shape[-1] // 4, 4)
    u = w[..., 0] | (w[..., 1] << 8) | (w[..., 2] << 16) | (w[..., 3] << 24)
    return jax.lax.bitcast_convert_type(u, jnp.int32)


def _prefix_meta(prefixes: jax.Array, prefix_lens: jax.Array,
                 L: int) -> jax.Array:
    """Per-prefix scalar table, flattened for SMEM: packed prefix words,
    byte masks (bytes < len), then [words to visit, boundary word,
    boundary shift, boundary check].  Mirrors ref.prefix_search_ref: the
    byte after the prefix must be 0 or '/' unless the prefix ends in '/'
    or fills the whole width."""
    Q, Lp = prefixes.shape
    plen = prefix_lens.astype(jnp.int32)
    within = jnp.arange(Lp, dtype=jnp.int32)[None, :] < plen[:, None]
    words = _pack_words(prefixes)
    masks = _pack_words(jnp.where(within, 255, 0).astype(jnp.uint8))
    n_vis = (jnp.minimum(plen, L) + 3) // 4
    nxt = jnp.minimum(plen, L - 1)
    last = jnp.take_along_axis(
        prefixes, jnp.maximum(plen - 1, 0)[:, None], axis=1)[:, 0]
    check = (plen < L) & (last != ord("/"))
    meta = jnp.stack([n_vis, nxt // 4, (nxt % 4) * 8,
                      check.astype(jnp.int32)], axis=1)
    return jnp.concatenate([words, masks, meta], axis=1).reshape(-1)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def prefix_search(tokens: jax.Array, prefixes: jax.Array,
                  prefix_lens: jax.Array, *, block_n: int = 4096,
                  interpret: bool) -> jax.Array:
    """tokens: (N, L) uint8; prefixes: (Q, L) uint8; prefix_lens: (Q,) int32.
    Returns the (Q, N) bool match bitmap.  ``block_n`` must be a multiple
    of 4096 (32 int8 sublanes × 128 lanes) or cover the whole padded
    table."""
    N, L = tokens.shape
    Q = prefixes.shape[0]
    L4 = -(-L // 4) * 4
    W = L4 // 4
    n_rows = -(-N // 128)                    # 128-path lane rows
    bs = max(1, min(block_n // 128, n_rows))
    n_rows = -(-n_rows // bs) * bs
    Np = n_rows * 128
    # padding: extra bytes are zero (beyond every prefix), extra paths are
    # 255s (match no real prefix); both are sliced off or masked
    toks = jnp.pad(tokens, ((0, 0), (0, L4 - L)))
    toks = jnp.pad(toks, ((0, Np - N), (0, 0)), constant_values=255)
    tw = _pack_words(toks).T.reshape(W, n_rows, 128)
    meta = _prefix_meta(jnp.pad(prefixes, ((0, 0), (0, L4 - L))),
                        prefix_lens, L)
    out = pl.pallas_call(
        functools.partial(_prefix_kernel, n_words=W),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_rows // bs,),
            in_specs=[pl.BlockSpec((W, bs, 128), lambda nb, m: (0, nb, 0))],
            out_specs=pl.BlockSpec((Q, bs, 128), lambda nb, m: (0, nb, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((Q, n_rows, 128), jnp.int8),
        interpret=interpret,
    )(meta, tw)
    return out.reshape(Q, Np)[:, :N].astype(jnp.bool_)
