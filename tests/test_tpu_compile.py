"""The main path's kernels compile for a TPU v5e at their real sizes.

Each test lowers one Pallas kernel with ``interpret=False`` against a
*described* v5e chip (no chip attached — the TPU compiler runs here) and
asserts the compiled program holds the Mosaic ``tpu_custom_call``.  What
the chip's compiler refuses (unsupported primitives, misaligned blocks,
scoped-VMEM overflow) fails here instead of on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so only the worker that
runs this file may touch it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.path_lookup import PIN_TILE, path_lookup
from repro.kernels.prefix_search import prefix_search


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_path_lookup_compiles_at_2pow20(one_chip):
    n, q, n_pin = 1 << 20, 256, 33 * PIN_TILE

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    u32 = jnp.uint32
    txt = _compiled_text(
        lambda kh, kl, qh, ql, ph, plo, pp: path_lookup(
            kh, kl, qh, ql, pinned=(ph, plo, pp), interpret=False),
        s((n,), u32), s((n,), u32), s((q,), u32), s((q,), u32),
        s((n_pin,), u32), s((n_pin,), u32), s((n_pin,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_prefix_search_compiles_at_2pow20(one_chip):
    n, width, q = 1 << 20, 96, 64

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    txt = _compiled_text(
        lambda t, p, ln: prefix_search(t, p, ln, interpret=False),
        s((n, width), jnp.uint8), s((q, width), jnp.uint8),
        s((q,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_decode_attention_compiles_at_router_serving_shapes(one_chip):
    cfg = get_config("wikikv-router")
    batch, max_len = 4, 256          # chip_smoke.py's serving lanes

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    kv = s((batch, cfg.n_kv_heads, max_len, cfg.d_head), jnp.float32)
    txt = _compiled_text(
        lambda q, k, v, ln: decode_attention(q, k, v, ln, interpret=False),
        s((batch, cfg.n_heads, cfg.d_head), jnp.float32), kv, kv,
        s((batch,), jnp.int32))
    assert "tpu_custom_call" in txt
