"""Jit'd dispatch wrappers over the Pallas kernels.

Public entry points used by models/ and core/.  The dispatch rule:

  * On a TPU backend every op runs its Pallas kernel, compiled
    (``interpret=False``).  There is no fallback: a shape the kernel
    cannot take raises.
  * Elsewhere (CPU) the ops run the jitted pure-jnp references in
    ``ref.py``, so XLA sees fusible HLO — interpret-mode Pallas inside a
    256-device SPMD lowering would be both meaningless and slow.  Set
    ``REPRO_FORCE_PALLAS=1`` to run the kernels in interpret mode instead
    (the kernel-path test leg).
"""
from __future__ import annotations

import os

import jax

from . import ref
from .decode_attention import decode_attention as _decode_pallas
from .flash_attention import flash_attention as _flash_pallas
from .moe_router import moe_router as _router_pallas
from .path_lookup import pad_keys, pad_pinned, path_lookup as _lookup_pallas
from .prefix_search import prefix_search as _prefix_pallas
from .rmsnorm import rmsnorm as _rmsnorm_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas() -> bool:
    return _on_tpu() or os.environ.get("REPRO_FORCE_PALLAS") == "1"


# ---------------------------------------------------------------------------
def attention(q, k, v, *, causal: bool = True, sm_scale: float | None = None,
              block_q: int = 128, block_k: int = 128):
    """(B, Hq, Sq, D) × (B, Hkv, Skv, D)² → (B, Hq, Sq, D)."""
    if _use_pallas():
        return _flash_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                             block_q=block_q, block_k=block_k,
                             interpret=not _on_tpu())
    skv = k.shape[2]
    if skv > 1024 and skv % 1024 == 0:
        # chunked online-softmax path: O(Sq·chunk) peak memory — the form
        # the dry-run lowers so 32k prefill fits HBM
        return ref.chunked_attention_ref(q, k, v, causal=causal,
                                         sm_scale=sm_scale, chunk=1024)
    return ref.attention_ref(q, k, v, causal=causal, sm_scale=sm_scale)


def decode_attention(q, k_cache, v_cache, lengths, *,
                     sm_scale: float | None = None, block_k: int = 256):
    """(B, Hq, D) × (B, Hkv, S, D)² × (B,) → (B, Hq, D)."""
    if _use_pallas():
        return _decode_pallas(q, k_cache, v_cache, lengths,
                              sm_scale=sm_scale, block_k=block_k,
                              interpret=not _on_tpu())
    return ref.decode_attention_ref(q, k_cache, v_cache, lengths,
                                    sm_scale=sm_scale)


def moe_router(logits, k: int, *, renormalize: bool = True,
               block_t: int = 256):
    """(T, E) → (weights (T,k) f32, indices (T,k) i32)."""
    if _use_pallas():
        return _router_pallas(logits, k, renormalize=renormalize,
                              block_t=block_t, interpret=not _on_tpu())
    return ref.moe_router_ref(logits, k, renormalize=renormalize)


def rmsnorm(x, scale=None, eps: float = 1e-6, block_t: int = 256):
    if _use_pallas():
        return _rmsnorm_pallas(x, scale, eps=eps, block_t=block_t,
                               interpret=not _on_tpu())
    return ref.rmsnorm_ref(x, scale, eps=eps)


def path_lookup(keys_hi, keys_lo, q_hi, q_lo, *, pinned=None,
                block_q: int = 256):
    """Sorted-table batched GET.  The kernel needs keys pre-padded via
    pad_keys (it raises otherwise); the reference accepts any length.
    ``pinned`` is the optional VMEM hot-set staging triple (hi, lo,
    sorted-table position) — the kernel probes it before touching the HBM
    table; the reference oracle applies the same short-circuit.  The
    reference is jitted here — the batched QueryEngine calls this once
    per engine round trip, so an eagerly-traced fori_loop would dominate
    the call."""
    if _use_pallas():
        return _lookup_pallas(keys_hi, keys_lo, q_hi, q_lo, pinned=pinned,
                              block_q=block_q, interpret=not _on_tpu())
    if pinned is not None:
        return _path_lookup_pinned_ref_jit(keys_hi, keys_lo, q_hi, q_lo,
                                           *pinned)
    return _path_lookup_ref_jit(keys_hi, keys_lo, q_hi, q_lo)


_path_lookup_ref_jit = jax.jit(ref.path_lookup_ref)
_path_lookup_pinned_ref_jit = jax.jit(ref.path_lookup_pinned_ref)


def prefix_search(tokens, prefixes, prefix_lens, *, block_n: int = 4096):
    """(N, L) × (Q, L) → (Q, N) bitmap.

    The batched QueryEngine path sends whole prefix batches here, so the
    reference vmaps the single-prefix oracle over the query axis — one
    XLA call per batch, matching the kernel's launch granularity."""
    if _use_pallas():
        return _prefix_pallas(tokens, prefixes, prefix_lens,
                              block_n=block_n, interpret=not _on_tpu())
    return _prefix_ref_batched(tokens, prefixes, prefix_lens)


@jax.jit
def _prefix_ref_batched(tokens, prefixes, prefix_lens):
    return jax.vmap(lambda p, n: ref.prefix_search_ref(tokens, p, n))(
        prefixes, prefix_lens)


__all__ = ["attention", "decode_attention", "moe_router", "rmsnorm",
           "path_lookup", "prefix_search", "pad_keys", "pad_pinned"]
