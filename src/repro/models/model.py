"""Public model facade: build, init, shard, step.

* ``abstract_params(cfg)``  — shapes-only params via jax.eval_shape (the
  dry-run path: no allocation ever happens for the full configs).
* ``param_shardings(cfg, mesh)`` — NamedSharding tree matching params.
* ``make_train_step(cfg, opt_cfg, mesh)`` — loss + grad + AdamW update,
  jit-able, shard-annotated.
* ``make_serve_step(cfg, mesh)`` — one-token decode over the cache.
* ``input_specs(cfg, shape)`` — ShapeDtypeStruct stand-ins for every model
  input of an assigned (arch × shape) cell, weak-type-correct, shardable.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..optim.adamw import AdamWConfig, adamw_init, adamw_update
from ..optim.schedule import cosine_schedule
from .config import ModelConfig
from . import transformer as T
from .layers import TP


@dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def init_params(cfg: ModelConfig, seed: int = 0):
    params, _ = T.init_params(jax.random.PRNGKey(seed), cfg)
    return params


def abstract_params(cfg: ModelConfig):
    """Shape/dtype tree without allocation."""
    return jax.eval_shape(lambda k: T.init_params(k, cfg)[0],
                          jax.random.PRNGKey(0))


def param_shardings(cfg: ModelConfig, mesh):
    """NamedSharding tree matching the param tree."""
    specs = spec_tree(cfg)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))


_SPEC_CACHE: dict[str, Any] = {}


def spec_tree(cfg: ModelConfig):
    """PartitionSpec tree (no allocation: captured from an abstract trace —
    init builds specs structurally, so tracing under eval_shape yields them
    without materializing a single parameter)."""
    key = repr(cfg)
    if key not in _SPEC_CACHE:
        cell: dict[str, Any] = {}

        def f(k):
            params, specs = T.init_params(k, cfg)
            cell["specs"] = specs
            return params

        jax.eval_shape(f, jax.random.PRNGKey(0))
        _SPEC_CACHE[key] = cell["specs"]
    return _SPEC_CACHE[key]


def opt_spec_tree(params_specs, opt_cfg: AdamWConfig, cfg: ModelConfig,
                  abstract=None):
    """Moment shardings.  f32/bf16 moments mirror the params; int8
    block-quantized moments are (blocks, 256) — shard the block dim over
    the FSDP axis for leaves big enough to quantize (adamw._QUANT_MIN)."""
    from ..optim.adamw import _leaf_quantized
    if opt_cfg.state_dtype == "int8":
        if abstract is None:
            abstract = abstract_params(cfg)

        def qspec(s, a):
            if _leaf_quantized(a):
                full = tuple(s) + (None,) * (len(a.shape) - len(tuple(s)))
                # q mirrors the param's sharding exactly; per-row scale
                # drops the last (block) dim
                return {"q": P(*full), "scale": P(*full[:-1])}
            return s
        m = jax.tree.map(qspec, params_specs, abstract,
                         is_leaf=lambda s: isinstance(s, P))
        return {"m": m, "v": m, "step": P()}
    return {
        "m": params_specs,
        "v": params_specs,
        "step": P(),
    }


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, per assigned shape)
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    B, S = shape.global_batch, shape.seq_len
    f = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        batch: dict[str, Any] = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        if cfg.frontend == "vision_stub":
            batch["prefix_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_prefix_embeds, cfg.d_model), f)
        if cfg.is_encdec:
            batch["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), f)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if cfg.frontend == "vision_stub":
            batch["prefix_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_prefix_embeds, cfg.d_model), f)
        if cfg.is_encdec:
            batch["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), f)
        return batch
    # decode: one new token against a cache of size S
    batch = {
        "tokens": jax.ShapeDtypeStruct((B,), jnp.int32),
        "lengths": jax.ShapeDtypeStruct((B,), jnp.int32),
    }
    if cfg.is_encdec:
        batch["enc_out"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), f)
    return batch


def abstract_decode_state(cfg: ModelConfig, batch: int, max_len: int):
    # batch/max_len are static shape inputs — close over them
    return jax.eval_shape(lambda: T.init_decode_state(cfg, batch, max_len))


def decode_state_specs(cfg: ModelConfig, batch: int, dp="data",
                       dp_size: int = 16, cache_layout: str = "auto",
                       tp_size: int = 16):
    """Sharding for the decode state.

    ``cache_layout`` (§Perf hillclimb B — see EXPERIMENTS.md):
      "seq"      — baseline: cache sequence dim over TP (context-parallel
                   KV).  Correct, but the per-step scatter at position
                   ``lengths`` crosses shard boundaries: GSPMD falls back
                   to "involuntary full rematerialization" (its own
                   warning) — the whole cache is re-gathered per layer per
                   token.  Measured tx = 1.2 s/token on qwen3 decode_32k.
      "head_dim" — shard the *head_dim* over TP.  The per-step cache write
                   is local to every shard; attention pays one (B, H, S)
                   psum for the Dh-partial logits instead.  The cache
                   memory per device is identical (Dh/16 × full S).
      "kv_head"  — MHA-class archs (n_kv_heads % tp == 0: codeqwen/olmo/
                   whisper): shard the KV-head dim itself — attention and
                   the cache write are *fully local per shard*, zero
                   decode collectives (§Perf B iteration 3; the head_dim
                   psum regressed exactly these archs).
      "auto"     — kv_head when divisible, else head_dim (default).

    Recurrent states (SSM / xLSTM) shard batch over data, features over TP.
    ``dp`` may be an axis name, a tuple of names, or None (batch too small).
    """
    b = dp if (batch % max(dp_size, 1) == 0 and batch >= dp_size) else None
    # with batch unshardable (long_500k B=1), put the sequence over data —
    # the cache is the only multi-GB tensor and must spread somewhere
    seq_axis = None if b is not None else dp

    if cache_layout in ("auto", "head_dim", "kv_head"):
        use_kv = (cfg.n_kv_heads % max(tp_size, 1) == 0
                  if cache_layout == "auto" else cache_layout == "kv_head")
    else:
        use_kv = False
    if cache_layout == "seq":
        attn_spec = P(None, b, None, TP, None)
        prefix_spec = P(b, None, TP, None)
    elif use_kv:
        attn_spec = P(None, b, TP, seq_axis, None)
        prefix_spec = P(b, TP, seq_axis, None)
    else:
        attn_spec = P(None, b, None, seq_axis, TP)
        prefix_spec = P(b, None, seq_axis, TP)

    def per_slot(kind):
        if kind == "attn":
            return {"k": attn_spec, "v": attn_spec}
        if kind == "mamba":
            return (P(None, b, None, TP),    # conv (periods, B, w, Din)
                    P(None, b, TP, None))    # h    (periods, B, Din, N)
        if kind == "mlstm":
            return (P(None, b, None, None, None),
                    P(None, b, None, None),
                    P(None, b, None))
        if kind == "slstm":
            z = P(None, b, TP)
            return (z, z, z, z)
        raise ValueError(kind)

    specs: dict[str, Any] = {}
    for s_idx, kind in enumerate(cfg.block_pattern):
        specs[f"slot{s_idx}"] = per_slot(kind)
    if cfg.n_dense_prefix:
        one = {"k": prefix_spec, "v": prefix_spec}
        specs["prefix"] = [one for _ in range(cfg.n_dense_prefix)]
    return specs


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig, mesh=None,
                    total_steps: int = 10000, warmup: int | None = None):
    wu = warmup if warmup is not None else max(1, min(200, total_steps // 20))

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, batch, cfg, mesh))(params)
        lr_scale = cosine_schedule(opt_state["step"], warmup=wu,
                                   total=total_steps)
        new_params, new_opt = adamw_update(params, grads, opt_state,
                                           opt_cfg, lr_scale=lr_scale)
        return new_params, new_opt, {"loss": loss, "lr_scale": lr_scale}
    return train_step


def make_eval_step(cfg: ModelConfig, mesh=None):
    def eval_step(params, batch):
        return T.loss_fn(params, batch, cfg, mesh)
    return eval_step


def make_prefill_step(cfg: ModelConfig, mesh=None):
    def prefill_step(params, batch):
        return T.forward(params, batch, cfg, mesh)
    return prefill_step


def make_serve_step(cfg: ModelConfig, mesh=None):
    def serve_step(params, state, batch):
        enc_out = batch.get("enc_out")
        logits, new_state = T.decode_step(
            params, state, batch["tokens"], batch["lengths"], cfg,
            mesh=mesh, enc_out=enc_out)
        # mask vocab-padding ids (embed table is padded to a 256 multiple)
        if cfg.padded_vocab != cfg.vocab:
            valid = jnp.arange(cfg.padded_vocab) < cfg.vocab
            logits = jnp.where(valid[None, :], logits, -jnp.inf)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, new_state
    return serve_step


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) for the roofline
    'useful compute' ratio.  N counted from the *active* parameter set."""
    n_active = _active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens  # forward only
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n_active * tokens


def _active_params(cfg: ModelConfig) -> float:
    """Parameters touched per token (MoE: top_k + shared experts only)."""
    shapes = jax.tree.leaves(
        jax.tree.map(lambda x: x.shape,
                     abstract_params(cfg),
                     is_leaf=lambda x: hasattr(x, "shape")))
    # count full tree, then correct the MoE expert stacks
    total = 0.0
    tree = abstract_params(cfg)
    flat, _ = jax.tree.flatten_with_path(tree)
    for path, leaf in flat:
        n = float(np.prod(leaf.shape))
        keys = "/".join(str(p) for p in path)
        if cfg.moe is not None and ("w_gate" in keys or "w_up" in keys
                                    or "w_down" in keys) and "moe" in keys:
            n = n * (cfg.moe.top_k / cfg.moe.n_experts)
        total += n
    return total
