"""Multi-device semantics (8 virtual CPU devices via subprocess — keeps
the main test process at 1 device as required).

Checks: (a) expert-parallel MoE ≡ single-device MoE, (b) the GPipe
schedule ≡ sequential stage application, (c) sharded train_step runs and
matches the unsharded loss, (d) a tiny dry-run cell lowers+compiles.
"""
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))

# (a) EP MoE == local MoE
from repro.configs import get_config
from repro.models import moe as MOE
from repro.models import transformer as T
from repro.models import model as M
from dataclasses import replace
cfg = get_config("dbrx-132b").reduced()
cfg = replace(cfg, moe=replace(cfg.moe, n_experts=8, capacity_factor=8.0))
params, specs = T.init_params(jax.random.PRNGKey(0), cfg)
moe_p = params["body"]["slot0"]["moe"]
moe_p0 = jax.tree.map(lambda x: x[0], moe_p)   # one period slice
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
local = MOE.moe_apply(moe_p0, x, cfg, mesh=None)
with mesh:
    ep = jax.jit(lambda p, x: MOE.moe_apply(p, x, cfg, mesh=mesh))(moe_p0, x)
err = float(jnp.max(jnp.abs(local - ep)))
assert err < 2e-4, f"EP vs local mismatch {err}"
print("EP==local OK", err)

# (b) pipeline schedule == sequential
from repro.distributed.pipeline import PipelineSchedule, pipeline_apply
pmesh = make_mesh((4, 2), ("pod", "model"))
S, Mb, F = 4, 6, 8
ws = jax.random.normal(jax.random.PRNGKey(2), (S, F, F)) * 0.3
xs = jax.random.normal(jax.random.PRNGKey(3), (Mb, 5, F))
def stage_fn(w, x):
    return jnp.tanh(x @ w)
sched = PipelineSchedule(n_stages=S, n_micro=Mb, axis="pod")
with pmesh:
    got = jax.jit(lambda w, x: pipeline_apply(stage_fn, w, x, sched, pmesh))(ws, xs)
want = xs
for i in range(S):
    want = stage_fn(ws[i], want)
err = float(jnp.max(jnp.abs(got - want)))
assert err < 1e-5, f"pipeline mismatch {err}"
print("pipeline OK", err, "bubble", sched.bubble_fraction)

# (c) sharded train step == unsharded loss
from repro.optim.adamw import AdamWConfig, adamw_init
cfg2 = get_config("qwen3-1.7b").reduced()
params2, _ = T.init_params(jax.random.PRNGKey(0), cfg2)
opt = adamw_init(params2, AdamWConfig())
rng = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(rng.randint(4, cfg2.vocab, (4, 32)), jnp.int32),
         "labels": jnp.asarray(rng.randint(4, cfg2.vocab, (4, 32)), jnp.int32)}
loss_1dev = float(T.loss_fn(params2, batch, cfg2))
pspecs = M.spec_tree(cfg2)
pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                      is_leaf=lambda s: isinstance(s, P))
step = M.make_train_step(cfg2, AdamWConfig(), mesh)
with mesh:
    p2, o2, aux = jax.jit(step)(jax.device_put(params2, pshard), opt, batch)
loss_8dev = float(aux["loss"])
assert abs(loss_1dev - loss_8dev) < 5e-2, (loss_1dev, loss_8dev)
print("sharded train OK", loss_1dev, loss_8dev)

# (d) tiny dry-run style lower+compile on a 2x4 mesh (full API path)
bshard = {k: NamedSharding(mesh, P("data") if v.ndim == 1 else P("data", None))
          for k, v in batch.items()}
jitted = jax.jit(step, in_shardings=(pshard, None, bshard))
with mesh:
    compiled = jitted.lower(params2, opt, batch).compile()
assert compiled.cost_analysis() is not None
print("lower+compile OK")
print("ALL DISTRIBUTED OK")
"""


@pytest.mark.slow
def test_distributed_semantics():
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
        capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    assert "ALL DISTRIBUTED OK" in res.stdout
