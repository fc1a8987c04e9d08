"""Pallas kernel sweeps: shapes × dtypes vs the pure-jnp oracles,
interpret mode (kernel-body semantics validated on CPU; TPU is target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_router import moe_router
from repro.kernels.path_lookup import pad_keys, path_lookup
from repro.kernels.prefix_search import prefix_search
from repro.kernels.rmsnorm import rmsnorm

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Skv,D,causal",
    [
        (2, 4, 2, 64, 64, 32, True),
        (1, 8, 1, 32, 128, 16, True),     # chunked prefill: Sq < Skv
        (2, 2, 2, 64, 64, 64, False),
        (1, 4, 4, 128, 128, 8, True),
    ])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D, causal, dtype):
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (B, Hq, Sq, D), dtype)
    k = jax.random.normal(kk, (B, Hkv, Skv, D), dtype)
    v = jax.random.normal(kv, (B, Hkv, Skv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,S,D,block_k",
    [(2, 8, 4, 256, 32, 64), (1, 4, 1, 512, 64, 128), (3, 2, 2, 128, 16, 32)])
def test_decode_attention_sweep(B, Hq, Hkv, S, D, block_k, dtype):
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (B, Hq, D), dtype)
    kc = jax.random.normal(kk, (B, Hkv, S, D), dtype)
    vc = jax.random.normal(kv, (B, Hkv, S, D), dtype)
    lens = jnp.asarray([(S // 2 + 7 * i) % S + 1 for i in range(B)], jnp.int32)
    out = decode_attention(q, kc, vc, lens, block_k=block_k, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_chunked_attention_matches_full():
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (2, 4, 2048, 32))
    k = jax.random.normal(kk, (2, 2, 2048, 32))
    v = jax.random.normal(kv, (2, 2, 2048, 32))
    a = ref.attention_ref(q, k, v, causal=True)
    b = ref.chunked_attention_ref(q, k, v, causal=True, chunk=256)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-6)


@pytest.mark.parametrize("T,E,k,bt", [(256, 16, 4, 64), (128, 384, 8, 128),
                                      (512, 8, 2, 256)])
def test_moe_router_sweep(T, E, k, bt):
    logits = jax.random.normal(KEY, (T, E), jnp.float32)
    w, i = moe_router(logits, k, block_t=bt, interpret=True)
    wr, ir = ref.moe_router_ref(logits, k)
    np.testing.assert_allclose(np.asarray(w), np.asarray(wr), atol=1e-6)
    assert jnp.all(i == ir)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,scaled", [((7, 130), True), ((4, 32, 64), True),
                                          ((16, 256), False)])
def test_rmsnorm_sweep(shape, scaled, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    s = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), jnp.float32) \
        if scaled else None
    out = rmsnorm(x, s, block_t=8, interpret=True)
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("N,Q,bq", [(1000, 301, 128), (130, 40, 32),
                                    (5000, 64, 64)])
def test_path_lookup_sweep(N, Q, bq):
    rs = np.random.RandomState(N)
    keys64 = np.unique(rs.randint(0, 2**63, size=N).astype(np.uint64))
    khi = (keys64 >> np.uint64(32)).astype(np.uint32)
    klo = (keys64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    khi_p, klo_p = pad_keys(khi, klo)
    qidx = rs.randint(0, len(keys64), size=Q)
    qhi = np.concatenate([khi[qidx], np.array([1, 2], np.uint32)])
    qlo = np.concatenate([klo[qidx], np.array([3, 4], np.uint32)])
    got = path_lookup(jnp.asarray(khi_p), jnp.asarray(klo_p),
                      jnp.asarray(qhi), jnp.asarray(qlo), block_q=bq,
                      interpret=True)
    want = ref.path_lookup_ref(jnp.asarray(khi), jnp.asarray(klo),
                               jnp.asarray(qhi), jnp.asarray(qlo))
    assert jnp.all(got == want)


def test_prefix_search_semantics():
    paths = ["/", "/a", "/a/b", "/ab", "/a/bc", "/sources/digests/x", "/b/c"]
    L = 32
    toks = np.zeros((len(paths), L), np.uint8)
    for i, p in enumerate(paths):
        b = p.encode()
        toks[i, :len(b)] = np.frombuffer(b, np.uint8)
    prefs = np.zeros((2, L), np.uint8)
    for i, p in enumerate(["/a", "/sources"]):
        b = p.encode()
        prefs[i, :len(b)] = np.frombuffer(b, np.uint8)
    plens = np.array([2, 8], np.int32)
    bm = np.asarray(prefix_search(jnp.asarray(toks), jnp.asarray(prefs),
                                  jnp.asarray(plens), block_n=4,
                                  interpret=True))
    col = bm[0]
    assert col[1] and col[2] and col[4]
    assert not col[3] and not col[0]       # "/ab" and "/" excluded
    assert bm[1, 5] and bm[1].sum() == 1


@pytest.mark.parametrize("N,L,Q,bn", [(100, 48, 3, 32), (513, 96, 5, 128)])
def test_prefix_search_sweep(N, L, Q, bn):
    rs = np.random.RandomState(L)
    alphabet = np.frombuffer(b"abcd/", np.uint8)
    toks = alphabet[rs.randint(0, 5, size=(N, L))].astype(np.uint8)
    toks[:, 0] = ord("/")
    prefs = alphabet[rs.randint(0, 5, size=(Q, L))].astype(np.uint8)
    prefs[:, 0] = ord("/")
    plens = rs.randint(1, 10, size=Q).astype(np.int32)
    got = prefix_search(jnp.asarray(toks), jnp.asarray(prefs),
                        jnp.asarray(plens), block_n=bn, interpret=True)
    want = jnp.stack(
        [ref.prefix_search_ref(jnp.asarray(toks), jnp.asarray(prefs[i]),
                               jnp.asarray(plens[i])) for i in range(Q)],
        axis=0)
    assert jnp.all(got == want)


@pytest.mark.parametrize("N,Q,n_pin,bq", [(1000, 301, 5, 128),
                                          (130, 40, 1, 32),
                                          (5000, 64, 33, 64),
                                          (512, 96, 0, 32)])
def test_path_lookup_pinned_parity(N, Q, n_pin, bq):
    """Level-0 VMEM pinned probe: kernel ≡ pinned oracle ≡ plain binary
    search (a consistent staging must never change any answer — pinned
    hits short-circuit, everything else falls through to HBM)."""
    from repro.kernels.path_lookup import pad_pinned
    rs = np.random.RandomState(N + n_pin)
    keys64 = np.unique(rs.randint(0, 2**63, size=N).astype(np.uint64))
    khi = (keys64 >> np.uint64(32)).astype(np.uint32)
    klo = (keys64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    khi_p, klo_p = pad_keys(khi, klo)
    # pin a deterministic subset; staged position == sorted-table rank
    pin_rows = rs.choice(len(keys64), size=min(n_pin, len(keys64)),
                         replace=False).astype(np.int32)
    pinned = pad_pinned(khi[pin_rows], klo[pin_rows], pin_rows)
    # queries: pinned hits, unpinned hits, misses
    qidx = rs.randint(0, len(keys64), size=Q)
    qhi = np.concatenate([khi[qidx], khi[pin_rows], np.array([1, 2], np.uint32)])
    qlo = np.concatenate([klo[qidx], klo[pin_rows], np.array([3, 4], np.uint32)])
    got = path_lookup(jnp.asarray(khi_p), jnp.asarray(klo_p),
                      jnp.asarray(qhi), jnp.asarray(qlo),
                      pinned=tuple(jnp.asarray(a) for a in pinned),
                      block_q=bq, interpret=True)
    oracle = ref.path_lookup_pinned_ref(
        jnp.asarray(khi), jnp.asarray(klo), jnp.asarray(qhi),
        jnp.asarray(qlo), *(jnp.asarray(a) for a in pinned))
    plain = ref.path_lookup_ref(jnp.asarray(khi), jnp.asarray(klo),
                                jnp.asarray(qhi), jnp.asarray(qlo))
    assert jnp.all(got == oracle)
    assert jnp.all(got == plain)
