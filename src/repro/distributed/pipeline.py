"""Pipeline parallelism over the "pod" axis (GPipe-style schedule).

The multi-pod mesh's "pod" axis defaults to data parallelism (DESIGN §5);
this module provides the alternative: split the layer stack into
``n_stages`` contiguous stages, one per pod, and stream ``n_micro``
microbatches through with the cross-stage hop expressed as
``jax.lax.ppermute`` over the pod axis — the collective XLA maps onto the
inter-pod links.

Implementation shape (single-program SPMD, shard_map over "pod"):
every pod holds its stage's parameters (stacked stage axis sharded over
"pod"); the schedule is the standard rotation — at step t, pod p runs
microbatch (t − p) through its stage and ppermutes its activation to
p+1.  Bubble fraction = (S−1)/(M+S−1); the EXPERIMENTS.md §Perf entry
compares this against pod-DP on collective bytes.

This is a *self-contained* reference used by tests (tiny configs) and by
the dry-run's alternative lowering (--pp flag in launch/train.py); the
main train path keeps pod-DP by default.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class PipelineSchedule:
    n_stages: int
    n_micro: int
    axis: str = "pod"

    @property
    def bubble_fraction(self) -> float:
        return (self.n_stages - 1) / (self.n_micro + self.n_stages - 1)


def pipeline_apply(stage_fn, stage_params, x_micro, sched: PipelineSchedule,
                   mesh):
    """Run microbatches through pipeline stages.

    stage_fn(params, x) -> x            — one stage's computation
    stage_params: pytree with leading (n_stages,) axis, sharded over pod
    x_micro: (n_micro, mb, ...) microbatched input (replicated)

    Returns (n_micro, mb, ...) outputs.  Total ticks = n_micro+n_stages−1;
    each tick every pod computes (or idles in the bubble) and activations
    rotate one hop — the 1-hop ppermute is the only inter-pod traffic.
    """
    S, M = sched.n_stages, sched.n_micro
    axis = sched.axis

    def body(params_stage, xs):
        # params_stage: this pod's stage slice — shard_map keeps the
        # (now size-1) stage axis; squeeze it.  xs: (M, mb, ...) replicated.
        params_stage = jax.tree.map(lambda a: a[0], params_stage)
        p = jax.lax.axis_index(axis)
        ticks = M + S - 1
        mb_shape = xs.shape[1:]
        carry_in = jnp.zeros(mb_shape, xs.dtype)   # activation arriving
        outs = jnp.zeros_like(xs)

        def tick(state, t):
            carry, outs = state
            mb_idx = t - p                          # microbatch at this pod
            active = (mb_idx >= 0) & (mb_idx < M)
            # stage 0 reads from the input stream; others from the carry
            x_in = jnp.where(p == 0,
                             xs[jnp.clip(mb_idx, 0, M - 1)], carry)
            y = stage_fn(params_stage, x_in)
            y = jnp.where(active, y, carry)
            # last stage writes the finished microbatch
            outs = jax.lax.cond(
                active & (p == S - 1),
                lambda o: o.at[jnp.clip(mb_idx, 0, M - 1)].set(y),
                lambda o: o, outs)
            # rotate activations forward one stage
            carry_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            return (carry_next, outs), None

        (carry, outs), _ = jax.lax.scan(tick, (carry_in, outs),
                                        jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them pod-wide
        outs = jax.lax.psum(
            jnp.where(p == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspec = P(axis)
    other = tuple(a for a in mesh.axis_names if a != axis)
    del other
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)
