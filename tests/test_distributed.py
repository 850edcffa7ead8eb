"""Distribution tests: run in a subprocess with 8 forced host devices so the
main pytest process keeps its single-device view."""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

out = {}

# ---- 1. sharded filtered ANN search == exact ground truth ----
# the exact scan of a ShardedFilteredIndex over four devices: each
# shard's scan left on its device, merged on the first one
from repro.data.ann_synth import DatasetSpec, synthesize, make_queries
from repro.ann.index import QueryBatch
from repro.ann.predicates import Predicate
from repro.ann.sharded import ShardedFilteredIndex
spec = DatasetSpec("t", 1600, 24, 40, 6, 8, 1.3, 2.0, 0.5, 0.3, 7)
ds = synthesize(spec)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
match = 0
with ShardedFilteredIndex(ds, 4, devices=jax.devices()[:4]) as sfx:
    for pred in (Predicate.EQUALITY, Predicate.AND, Predicate.OR):
        qs = make_queries(ds, pred, 8, seed=5)
        ids = sfx.search(QueryBatch(qs.vectors, qs.bitmaps, pred, 10),
                         "prefilter").ids
        for i in range(8):
            want = set(qs.ground_truth[i][qs.ground_truth[i] >= 0].tolist())
            got = set(ids[i][ids[i] >= 0].tolist())
            match += got == want
out["ann_match"] = match

# ---- 2. sharded train step runs and loss decreases ----
from repro.configs.base import get_smoke_config
from repro.launch import steps as ST
from repro.launch.mesh import mesh_axes
from repro.launch import specs as SP
from repro.models import lm, common
from repro.data.tokens import TokenStream
cfg = get_smoke_config("internlm2-1.8b")
axes = mesh_axes(mesh)
ctx = lm.ModelCtx(mesh=mesh, dp_axes=axes.dp_axes, tp_size=axes.tp_size,
                  dp_size=axes.dp_size, qc_train=32, gla_chunk=32)
params, opt = ST.init_train_state(cfg, jax.random.PRNGKey(0))
desc = lm.model_desc(cfg)
pspecs = SP.param_partition(desc, axes, fsdp=True)
params = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                      params, pspecs)
step = jax.jit(ST.make_train_step(cfg, ctx, accum=2))
stream = TokenStream(cfg.vocab, 32, 8, seed=1)
losses = []
with mesh:
    for i in range(8):
        batch = {k: jnp.asarray(v) for k, v in stream.batch(i).items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
out["loss_first"] = losses[0]
out["loss_last"] = losses[-1]

# ---- 3. elastic reshard: (4,2) -> (2,4) mesh ----
from repro.runtime import elastic_reshard
host = jax.tree.map(lambda x: np.asarray(x), params)
mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
axes2 = mesh_axes(mesh2)
pspecs2 = SP.param_partition(desc, axes2, fsdp=True)
params2 = elastic_reshard(host, pspecs2, mesh2)
ctx2 = lm.ModelCtx(mesh=mesh2, dp_axes=axes2.dp_axes, tp_size=axes2.tp_size,
                   dp_size=axes2.dp_size, qc_train=32, gla_chunk=32)
step2 = jax.jit(ST.make_train_step(cfg, ctx2, accum=2))
with mesh2:
    batch = {k: jnp.asarray(v) for k, v in stream.batch(100).items()}
    params2, opt2, m2 = step2(params2, jax.device_put(opt), batch)
out["elastic_loss"] = float(m2["loss"])

# ---- 4. MoE shard_map path on a real multi-device mesh ----
cfg_moe = get_smoke_config("grok-1-314b")
params_m, opt_m = ST.init_train_state(cfg_moe, jax.random.PRNGKey(0))
step_m = jax.jit(ST.make_train_step(cfg_moe, ctx, accum=1))
stream_m = TokenStream(cfg_moe.vocab, 32, 8, seed=2)
with mesh:
    batch = {k: jnp.asarray(v) for k, v in stream_m.batch(0).items()}
    _, _, mm = step_m(params_m, opt_m, batch)
out["moe_loss"] = float(mm["loss"])

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def subproc_results():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, env=env, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sharded_ann_exact(subproc_results):
    assert subproc_results["ann_match"] == 24


def test_sharded_training_loss_decreases(subproc_results):
    assert subproc_results["loss_last"] < subproc_results["loss_first"]


def test_elastic_reshard_step(subproc_results):
    import math
    assert math.isfinite(subproc_results["elastic_loss"])


def test_moe_shard_map(subproc_results):
    import math
    assert math.isfinite(subproc_results["moe_loss"])
