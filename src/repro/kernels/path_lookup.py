"""Batched path-hash lookup (the paper's Q1/GET, TPU-native) — Pallas kernels.

The WikiKV point lookup — ``GET(H(π))`` over the sorted 64-bit digest
table — becomes a *batched* device op: the serving tier resolves a whole
navigation batch (thousands of concurrent GET/LS steps) in one launch.

Two kernels, designed around the TPU memory hierarchy instead of the LSM
pread of the paper.  The digest table is viewed as (N/TILE, TILE) rows;
every row's first key is a *fence*.

  route (levels 0 and 1, pure vector work):
    level 0 (pinned): the engine stages the wiki's pinned hot set ("/" +
      every dimension — the paper's L1 cache tier) as a VMEM-resident
      sub-table of (hi, lo, sorted-table position) triples.  Every query
      is broadcast-compared against it first; a pinned hit is encoded as
      ``-1 - position`` and never touches the HBM table, so the hot rows
      that dominate navigation traffic (every chain starts at "/" and a
      dimension) cost zero HBM touches.
    level 1 (fences): the fence column streams through VMEM in
      ``block_f`` tiles along the grid; each query's tile id is the number
      of fences <= q minus one, a lexicographic broadcast compare reduced
      by row-sum and accumulated in the resident output block.  The
      working set is (block_q, block_f) whatever N is.
  probe (level 2): the route codes arrive by scalar prefetch in SMEM.
    For each query of the block, one async copy brings its candidate tile
    (TILE keys of each digest half) from the HBM table into a VMEM
    scratch row; after the copies land, one (block_q, TILE) exact compare
    emits the row id (or −1) for the whole block.

This replaces the per-query binary search (log₂N dependent HBM loads,
latency-bound) with exactly one tile copy per unpinned query — the O(1)
storage-round-trip contract of §IV, realized as "O(1) HBM touches per
query".  ``pinned=None`` degrades to a sentinel table that can never hit.

Ordering compares run on int32 after flipping the sign bit of each
uint32 digest half (order-preserving), so the kernels need no unsigned
vector compares.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128

#: pinned sub-table allocation granule (lane-friendly; tiny either way)
PIN_TILE = 8

#: fences compared per route grid step: bounds the (block_q, block_f)
#: compare matrix in VMEM independently of the table size
BLOCK_F = 1024

_SIGN = np.uint32(0x80000000)
#: 0xFFFFFFFF after the sign-bit flip of ``_ordered``
_MAX_ORDERED = np.int32(0x7FFFFFFF)


def _ordered(x: jax.Array) -> jax.Array:
    """uint32 → int32 with the same ordering (sign-bit flip)."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.uint32) ^ _SIGN,
                                        jnp.int32)


def _route_kernel(qhi_ref, qlo_ref, fhi_ref, flo_ref,
                  phi_ref, plo_ref, ppos_ref, code_ref, *, n_fences: int):
    """Refs: queries q{hi,lo} (block_q, 1); fences f{hi,lo} (1, block_f);
    pinned p{hi,lo,pos} (1, P); out code (block_q, 1) int32, resident
    across the fence axis of the grid."""
    f = pl.program_id(1)
    qhi = qhi_ref[...]
    qlo = qlo_ref[...]
    le = (fhi_ref[...] < qhi) | ((fhi_ref[...] == qhi)
                                 & (flo_ref[...] <= qlo))
    count = jnp.sum(le.astype(jnp.int32), axis=1, keepdims=True)

    @pl.when(f == 0)
    def _init():
        code_ref[...] = count

    @pl.when(f > 0)
    def _accumulate():
        code_ref[...] += count

    @pl.when(f == pl.num_programs(1) - 1)
    def _finish():
        tile = jnp.clip(code_ref[...] - 1, 0, n_fences - 1)
        # pinned keys are unique, so the masked row-sum selects the hit
        eq = (phi_ref[...] == qhi) & (plo_ref[...] == qlo)     # (bq, P)
        hit = jnp.max(eq.astype(jnp.int32), axis=1, keepdims=True) > 0
        pos = jnp.sum(jnp.where(eq, ppos_ref[...], 0), axis=1,
                      keepdims=True)
        code_ref[...] = jnp.where(hit, -1 - pos, tile)


def _probe_kernel(code_sref, khi_hbm, klo_hbm, qhi_ref, qlo_ref, code_ref,
                  out_ref, bhi, blo, sem, *, block_q: int):
    """Refs: code (Qp,) SMEM (scalar prefetch); keys k{hi,lo} (N/TILE,
    TILE) in HBM; queries q{hi,lo} and code (block_q, 1) VMEM; out
    (block_q, 1) int32; scratch b{hi,lo} (block_q, TILE) VMEM + two DMA
    semaphores."""
    base = pl.program_id(0) * block_q

    def copies(i, tile):
        return (pltpu.make_async_copy(khi_hbm.at[pl.ds(tile, 1)],
                                      bhi.at[pl.ds(i, 1)], sem.at[0]),
                pltpu.make_async_copy(klo_hbm.at[pl.ds(tile, 1)],
                                      blo.at[pl.ds(i, 1)], sem.at[1]))

    def start(i, carry):
        tile = code_sref[base + i]

        @pl.when(tile >= 0)
        def _():
            for cp in copies(i, tile):
                cp.start()

        return carry

    def wait(i, carry):
        tile = code_sref[base + i]

        @pl.when(tile >= 0)
        def _():
            for cp in copies(i, tile):
                cp.wait()

        return carry

    jax.lax.fori_loop(0, block_q, start, 0)
    jax.lax.fori_loop(0, block_q, wait, 0)
    code = code_ref[...]                                       # (bq, 1)
    # rows of pinned queries were never copied; their stale contents are
    # masked by the code select below
    eq = (bhi[...] == qhi_ref[...]) & (blo[...] == qlo_ref[...])
    lane = jax.lax.broadcasted_iota(jnp.int32, eq.shape, 1)
    first = jnp.min(jnp.where(eq, lane, TILE), axis=1, keepdims=True)
    found = jnp.where(first < TILE, code * TILE + first, -1)
    out_ref[...] = jnp.where(code < 0, -1 - code, found)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def path_lookup(keys_hi: jax.Array, keys_lo: jax.Array,
                q_hi: jax.Array, q_lo: jax.Array, *,
                pinned: tuple[jax.Array, jax.Array, jax.Array] | None = None,
                block_q: int = 256, interpret: bool) -> jax.Array:
    """keys_{hi,lo}: (N,) uint32 sorted pairs; q_{hi,lo}: (Q,) uint32.
    Returns (Q,) int32 row ids, −1 on miss.  N must be a TILE multiple:
    the caller pads with max-key sentinels (``pad_keys``).

    ``pinned`` is the optional VMEM hot-set staging: (pin_hi, pin_lo,
    pin_pos) where pin_pos[j] is the *sorted-table position* of the pinned
    key pair — the value the HBM search would have produced.  Free slots
    hold 0xFFFFFFFF key sentinels (match-proof; see pad_keys).

    ``block_q`` must be a multiple of 128 (or cover the whole batch) for
    the compiled TPU path."""
    n = keys_hi.shape[0]
    if n % TILE:
        raise ValueError(f"key table must be padded to {TILE}: {n}")
    Q = q_hi.shape[0]
    bq = min(block_q, Q)
    Qp = -(-Q // bq) * bq
    # padded queries are (0, 0): never an FNV digest, sliced off below
    qhi = jnp.pad(_ordered(q_hi), (0, Qp - Q)).reshape(Qp, 1)
    qlo = jnp.pad(_ordered(q_lo), (0, Qp - Q)).reshape(Qp, 1)
    khi = _ordered(keys_hi).reshape(n // TILE, TILE)
    klo = _ordered(keys_lo).reshape(n // TILE, TILE)
    n_fences = n // TILE
    bf = min(BLOCK_F, n_fences)
    n_fp = -(-n_fences // bf) * bf
    # fence padding is the (ordered) max-key sentinel: never <= a query
    fhi = jnp.pad(khi[:, 0], (0, n_fp - n_fences),
                  constant_values=_MAX_ORDERED).reshape(1, n_fp)
    flo = jnp.pad(klo[:, 0], (0, n_fp - n_fences),
                  constant_values=_MAX_ORDERED).reshape(1, n_fp)
    if pinned is None:
        pin_hi = jnp.full((PIN_TILE,), 0xFFFFFFFF, jnp.uint32)
        pin_lo = pin_hi
        pin_pos = jnp.zeros((PIN_TILE,), jnp.int32)
    else:
        pin_hi, pin_lo, pin_pos = pinned
    n_pin = pin_hi.shape[0]
    phi = _ordered(pin_hi).reshape(1, n_pin)
    plo = _ordered(pin_lo).reshape(1, n_pin)
    ppos = pin_pos.astype(jnp.int32).reshape(1, n_pin)

    q_spec = pl.BlockSpec((bq, 1), lambda qb, fb: (qb, 0))
    f_spec = pl.BlockSpec((1, bf), lambda qb, fb: (0, fb))
    p_spec = pl.BlockSpec((1, n_pin), lambda qb, fb: (0, 0))
    code = pl.pallas_call(
        functools.partial(_route_kernel, n_fences=n_fences),
        grid=(Qp // bq, n_fp // bf),
        in_specs=[q_spec, q_spec, f_spec, f_spec, p_spec, p_spec, p_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((Qp, 1), jnp.int32),
        interpret=interpret,
    )(qhi, qlo, fhi, flo, phi, plo, ppos)

    blk = pl.BlockSpec((bq, 1), lambda qb, code_s: (qb, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_probe_kernel, block_q=bq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Qp // bq,),
            in_specs=[hbm, hbm, blk, blk, blk],
            out_specs=blk,
            scratch_shapes=[pltpu.VMEM((bq, TILE), jnp.int32),
                            pltpu.VMEM((bq, TILE), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((Qp, 1), jnp.int32),
        interpret=interpret,
    )(code.reshape(Qp), khi, klo, qhi, qlo, code)
    return out[:Q, 0]


def pad_pinned(pin_hi: np.ndarray, pin_lo: np.ndarray, pin_pos: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad a pinned staging triple to the PIN_TILE granule with
    0xFFFFFFFF key sentinels (position 0 — never selected)."""
    n = pin_hi.shape[0]
    pad = (-n) % PIN_TILE if n else PIN_TILE
    if pad == 0:
        return pin_hi, pin_lo, pin_pos
    fill = np.full((pad,), 0xFFFFFFFF, dtype=np.uint32)
    return (np.concatenate([pin_hi, fill]),
            np.concatenate([pin_lo, fill]),
            np.concatenate([pin_pos, np.zeros((pad,), np.int32)]))


def pad_keys(keys_hi: np.ndarray, keys_lo: np.ndarray
             ) -> tuple[np.ndarray, np.ndarray]:
    """Pad the sorted key table to a TILE multiple with 0xFFFFFFFF
    sentinels (greater than every real key, so search order is preserved;
    collisions with a real all-ones key are impossible because FNV of a
    non-empty path never yields 2^64−1 — asserted at freeze time)."""
    n = keys_hi.shape[0]
    pad = (-n) % TILE
    if pad == 0:
        return keys_hi, keys_lo
    fill = np.full((pad,), 0xFFFFFFFF, dtype=np.uint32)
    return (np.concatenate([keys_hi, fill]),
            np.concatenate([keys_lo, fill]))
