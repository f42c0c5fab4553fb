"""Collective-schedule correctness on a multi-device mesh.

The pytest process sees one CPU device; these tests re-exec a small driver
in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 so
the schedules run on a real 8-way mesh. One subprocess runs ALL cases
(startup dominates)."""
import json
import os
import subprocess
import sys

import pytest

_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core import collectives as C
from repro.core.sync import SyncConfig, allreduce_int8_cps, sync_gradients

mesh = make_mesh((8,), ("x",))
results = {}
x = jnp.arange(8 * 40, dtype=jnp.float32).reshape(8, 40) / 7.0
want = np.asarray(x.sum(0))

for strat, fac in [("psum", None), ("ring", None), ("rhd", None),
                   ("cps", None), ("hcps", (4, 2)), ("hcps", (2, 4)),
                   ("hcps", (2, 2, 2))]:
    f = shard_map(lambda v: C.allreduce(v[0], "x", strat, factors=fac)[None],
                  mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    out = np.asarray(f(x))
    results[f"allreduce_{strat}_{fac}"] = bool(
        np.allclose(out, np.tile(want, (8, 1)), rtol=1e-5))

# reduce_scatter: shard i must hold the i-th slice of the summed vector
for strat, fac in [("ring", None), ("rhd", None), ("cps", None),
                   ("hcps", (4, 2))]:
    f = shard_map(lambda v: C.reduce_scatter(v[0], "x", strat,
                                             factors=fac)[None],
                  mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    out = np.asarray(f(x)).reshape(-1)
    results[f"rs_{strat}_{fac}"] = bool(np.allclose(out, want, rtol=1e-5))

# reduce_scatter SHAPE CONTRACT: every strategy — psum included — returns
# the FLAT (chunk,) shard; the old psum path (tiled=False on the (n, chunk)
# reshape) handed back a (1, chunk) slab instead.
shape_ok, value_ok = {}, {}
for strat, fac in [("psum", None), ("ring", None), ("rhd", None),
                   ("cps", None), ("hcps", (4, 2))]:
    f = shard_map(lambda v: C.reduce_scatter(v[0], "x", strat,
                                             factors=fac)[None],
                  mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    out = np.asarray(f(x))
    shape_ok[strat] = out.shape == (8, x.shape[1] // 8)
    value_ok[strat] = bool(np.allclose(out.reshape(-1), want, rtol=1e-5))
results["rs_shape_contract"] = all(shape_ok.values())
results["rs_shape_detail"] = {k: bool(v) for k, v in shape_ok.items()}
results["rs_value_contract"] = all(value_ok.values())

# non-power-of-two axes: executable RHD via fold-in/fold-out (the plans.rhd
# patch) — allreduce must match the sum on 3/5/6/7-device sub-meshes
npo2 = {}
for n in (3, 5, 6, 7):
    sub = make_mesh((n,), ("x",), devices=jax.devices()[:n])
    y2 = jnp.arange(n * 19, dtype=jnp.float32).reshape(n, 19) / 3.0
    f = shard_map(lambda v: C.allreduce(v[0], "x", "rhd")[None],
                  mesh=sub, in_specs=P("x"), out_specs=P("x"))
    npo2[n] = bool(np.allclose(np.asarray(f(y2)),
                               np.tile(np.asarray(y2.sum(0)), (n, 1)),
                               rtol=1e-5))
results["rhd_non_pow2"] = all(npo2.values())
results["rhd_non_pow2_detail"] = npo2

# odd sizes exercise padding
y = jnp.arange(8 * 13, dtype=jnp.float32).reshape(8, 13)
wanty = np.asarray(y.sum(0))
f = shard_map(lambda v: C.allreduce(v[0], "x", "hcps", factors=(2, 4))[None],
              mesh=mesh, in_specs=P("x"), out_specs=P("x"))
results["allreduce_pad"] = bool(
    np.allclose(np.asarray(f(y)), np.tile(wanty, (8, 1)), rtol=1e-5))

# int8-compressed CPS allreduce: lossy — check correlation, not exactness
g = jax.random.normal(jax.random.PRNGKey(0), (8, 1000))
f = shard_map(lambda v: allreduce_int8_cps(v[0], "x")[None],
              mesh=mesh, in_specs=P("x"), out_specs=P("x"))
out = np.asarray(f(g))[0]
ref = np.asarray(g.sum(0))
rel = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
results["int8_cps_rel"] = float(rel)
results["int8_cps_ok"] = bool(rel < 0.05)

# sync_gradients end-to-end over a pytree with gentree strategy
grads = {"a": jnp.ones((8, 100)), "b": jnp.full((8, 7), 2.0)}
def sync(g):
    loc = {k: v[0] for k, v in g.items()}
    out = sync_gradients(loc, [("x", 8)], SyncConfig(strategy="gentree"))
    return {k: v[None] for k, v in out.items()}
f = shard_map(sync, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
out = f(grads)
results["sync_gentree"] = bool(
    np.allclose(np.asarray(out["a"])[0], 8.0)
    and np.allclose(np.asarray(out["b"])[0], 16.0))

# multi-axis (pod × data) hierarchical sync
mesh2 = make_mesh((2, 4), ("pod", "data"))
z = jnp.arange(8 * 24, dtype=jnp.float32).reshape(2, 4, 24)
def sync2(v):
    out = sync_gradients({"g": v[0, 0]}, [("data", 4), ("pod", 2)],
                         SyncConfig(strategy="hcps", factors=(2, 2)))
    return {"g": out["g"][None, None]}
f = shard_map(sync2, mesh=mesh2, in_specs=P("pod", "data"),
              out_specs=P("pod", "data"))
out = np.asarray(f(z)["g"]).reshape(8, 24)
results["sync_two_axis"] = bool(
    np.allclose(out, np.tile(z.reshape(8, 24).sum(0), (8, 1)), rtol=1e-5))

print("RESULTS " + json.dumps(results))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _DRIVER], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


@pytest.mark.parametrize("key", [
    "allreduce_psum_None", "allreduce_ring_None", "allreduce_rhd_None",
    "allreduce_cps_None", "allreduce_hcps_(4, 2)", "allreduce_hcps_(2, 4)",
    "allreduce_hcps_(2, 2, 2)", "rs_ring_None", "rs_rhd_None",
    "rs_cps_None", "rs_hcps_(4, 2)", "allreduce_pad", "int8_cps_ok",
    "sync_gentree", "sync_two_axis",
    "rs_shape_contract", "rs_value_contract", "rhd_non_pow2"])
def test_collective(results, key):
    assert results[key] is True, (key, results)


_TOPK_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core.sync import allreduce_topk

mesh = make_mesh((8,), ("x",))
# sparse gradients: top-k with k covering all nonzeros must be EXACT
g = jnp.zeros((8, 1000))
g = g.at[:, :5].set(jax.random.normal(jax.random.PRNGKey(0), (8, 5)))
f = shard_map(lambda v: allreduce_topk(v[0], "x", k_frac=0.01)[None],
              mesh=mesh, in_specs=P("x"), out_specs=P("x"))
out = np.asarray(f(g))[0]
ref = np.asarray(g.sum(0))
print("RESULTS " + json.dumps({
    "exact_on_sparse": bool(np.allclose(out, ref, rtol=1e-5, atol=1e-6))}))
"""


def test_topk_allreduce_exact_on_sparse():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _TOPK_DRIVER], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULTS ")][-1]
    assert json.loads(line[len("RESULTS "):])["exact_on_sparse"]
