"""Executed-schedule ≡ lax.psum equivalence on a real multi-device mesh.

The acceptance bar for the executable Plan IR (DESIGN.md §8): every
compiled schedule — lowered flat builders AND lowered GenTree plans for
Table-6-style multi-level topologies — must produce results equal to
`lax.psum` within dtype tolerance when executed under shard_map on 8 host
CPU devices, across sizes, dtypes and axis sizes (including
non-powers-of-two); and `SyncConfig(strategy="plan")` must train a model
through launch.train on the executed plans, tracking the psum-sync loss
exactly.

Like test_collectives.py, one subprocess (XLA_FLAGS device-count=8) runs
every case; when hypothesis is installed the subprocess additionally runs
a randomized sweep (sizes × dtypes × axis sizes × topologies) and reports
any counterexample.
"""
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
from repro.core import plans, topology
from repro.core.gentree import gentree
from repro.core.lower import lower_plan

results = {}


def run_sched(cs, n, size, dtype, seed=0):
    mesh = make_mesh((n,), ("x",), devices=jax.devices()[:n])
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, size),
                          jnp.float32).astype(dtype)
    f = shard_map(lambda v: cs.allreduce(v[0], "x")[None],
                  mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    p = shard_map(lambda v: jax.lax.psum(v[0], "x")[None],
                  mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    got = np.asarray(jax.jit(f)(x)).astype(np.float64)
    want = np.asarray(jax.jit(p)(x)).astype(np.float64)
    scale = np.abs(want).max() + 1e-30
    return float(np.abs(got - want).max() / scale)


# ---- acceptance case: two-level Table-6-style topology, float32 @ 1e-6 ----
topo = topology.symmetric_tree(2, 4)     # 2 middle switches x 4 servers
r = gentree(topo, 1e6)
cs = lower_plan(r.plan)
results["table6_two_level_err"] = run_sched(cs, 8, 1000, jnp.float32)
results["table6_two_level"] = results["table6_two_level_err"] < 1e-6

# ---- lowered plans x sizes x dtypes ---------------------------------------
CASES = {
    "gentree_ss8": gentree(topology.single_switch(8), 1e6).plan,
    "gentree_cdc8": gentree(topology.cross_dc(
        dc0_middle=2, dc0_servers=2, dc1_middle=2, dc1_servers=2),
        1e6).plan,
    "ring": plans.ring(8, 80.0),
    "cps": plans.cps(8, 80.0),
    "rhd": plans.rhd(8, 80.0),
    "hcps4x2": plans.hcps([4, 2], 80.0),
    "reduce_broadcast": plans.reduce_broadcast(8, 80.0),
}
for name, plan in CASES.items():
    cs = lower_plan(plan)
    errs = []
    for size in (1, 8, 41, 1000):
        errs.append(run_sched(cs, 8, size, jnp.float32, seed=size))
    results[f"{name}_f32"] = max(errs) < 1e-6
    results[f"{name}_bf16"] = run_sched(cs, 8, 128, jnp.bfloat16) < 0.05

# ---- non-power-of-two axis sizes ------------------------------------------
for n in (3, 5, 6, 7):
    plan = gentree(topology.single_switch(n), 1e5).plan
    cs = lower_plan(plan)
    results[f"gentree_n{n}"] = run_sched(cs, n, 37, jnp.float32) < 1e-6
    cs_rhd = lower_plan(plans.rhd(n, float(n * 8)))
    results[f"rhd_n{n}"] = run_sched(cs_rhd, n, 37, jnp.float32) < 1e-6

# ---- RS/AG halves compose to the psum result ------------------------------
mesh = make_mesh((8,), ("x",))
x = jax.random.normal(jax.random.PRNGKey(3), (8, 41))
cs = lower_plan(gentree(topology.symmetric_tree(2, 4), 1e6).plan)
g = shard_map(lambda v: cs.reduce_scatter(v[0], "x")[None],
              mesh=mesh, in_specs=P("x"), out_specs=P("x"))
shards = np.asarray(jax.jit(g)(x))
pad = (-41) % 8
want = np.concatenate([np.asarray(x.sum(0)), np.zeros(pad, np.float32)])
results["rs_half"] = bool(np.allclose(shards.reshape(-1), want, atol=1e-5))
h = shard_map(lambda v: cs.all_gather(v[0], "x")[None],
              mesh=mesh, in_specs=P("x"), out_specs=P("x"))
full = np.asarray(jax.jit(h)(jnp.asarray(shards)))
results["ag_half"] = bool(np.allclose(full, np.tile(want, (8, 1)),
                                      atol=1e-5))

# ---- sync_gradients + allreduce_planned execute plans ---------------------
from repro.core.sync import SyncConfig, sync_gradients
from repro.core import collectives as C
grads = {"a": jnp.ones((8, 100)), "b": jnp.full((8, 7), 2.0)}
f = shard_map(
    lambda g: {k: v[None] for k, v in sync_gradients(
        {k: v[0] for k, v in g.items()}, [("x", 8)],
        SyncConfig(strategy="plan")).items()},
    mesh=mesh, in_specs=P("x"), out_specs=P("x"))
out = f(grads)
results["sync_plan"] = bool(
    np.allclose(np.asarray(out["a"])[0], 8.0)
    and np.allclose(np.asarray(out["b"])[0], 16.0))
f = shard_map(lambda v: C.allreduce_planned(v[0], "x")[None],
              mesh=mesh, in_specs=P("x"), out_specs=P("x"))
xa = jnp.arange(8 * 33, dtype=jnp.float32).reshape(8, 33)
results["allreduce_planned"] = bool(np.allclose(
    np.asarray(f(xa)), np.tile(np.asarray(xa.sum(0)), (8, 1)), rtol=1e-5))

# ---- multi-axis (pod x data) strategy="plan" ------------------------------
mesh2 = make_mesh((2, 4), ("pod", "data"))
z = jnp.arange(8 * 24, dtype=jnp.float32).reshape(2, 4, 24)
f = shard_map(
    lambda v: {"g": sync_gradients({"g": v[0, 0]}, [("data", 4), ("pod", 2)],
                                   SyncConfig(strategy="plan"))["g"][
        None, None]},
    mesh=mesh2, in_specs=P("pod", "data"), out_specs=P("pod", "data"))
out2 = np.asarray(f(z)["g"]).reshape(8, 24)
results["sync_plan_two_axis"] = bool(np.allclose(
    out2, np.tile(z.reshape(8, 24).sum(0), (8, 1)), rtol=1e-5))

# ---- training through launch.train with sync="plan" -----------------------
from repro.launch.train import TrainConfig, run_training
logs = []
out_plan = run_training(TrainConfig(
    arch="stablelm-12b", steps=2, engine="manual", sync="plan",
    seq_len=16, global_batch=8, log_every=10),
    on_log=logs.append)
out_psum = run_training(TrainConfig(
    arch="stablelm-12b", steps=2, engine="manual", sync="psum",
    seq_len=16, global_batch=8, log_every=10),
    on_log=logs.append)
dl = max(abs(a - b) for a, b in zip(out_plan["losses"],
                                    out_psum["losses"]))
results["train_plan_finite"] = bool(
    np.isfinite(out_plan["losses"]).all())
results["train_plan_matches_psum"] = bool(dl < 1e-3)
results["train_plan_loss_delta"] = float(dl)

# ---- hypothesis sweep (CI; skipped when hypothesis is absent) -------------
try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYP = True
except ImportError:
    HAVE_HYP = False
results["hypothesis_ran"] = HAVE_HYP
if HAVE_HYP:
    import math

    @settings(max_examples=12, deadline=None)
    @given(n=hst.integers(2, 8), size=hst.integers(1, 300),
           dtype=hst.sampled_from(["float32", "bfloat16"]),
           kind=hst.sampled_from(["gentree", "ring", "cps", "rhd"]),
           seed=hst.integers(0, 10**6))
    def sweep(n, size, dtype, kind, seed):
        if kind == "gentree":
            plan = gentree(topology.single_switch(n), 1e5).plan
        else:
            plan = getattr(plans, kind)(n, float(8 * n))
        cs = lower_plan(plan)
        tol = 1e-6 if dtype == "float32" else 0.05
        err = run_sched(cs, n, size, jnp.dtype(dtype), seed=seed)
        assert err < tol, (n, size, dtype, kind, err)

    try:
        sweep()
        results["hypothesis_sweep"] = True
    except Exception as e:
        results["hypothesis_sweep"] = False
        results["hypothesis_error"] = repr(e)[:500]

print("RESULTS " + json.dumps(results))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _DRIVER], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


@pytest.mark.parametrize("key", [
    "table6_two_level",
    "gentree_ss8_f32", "gentree_ss8_bf16",
    "gentree_cdc8_f32", "gentree_cdc8_bf16",
    "ring_f32", "ring_bf16", "cps_f32", "cps_bf16",
    "rhd_f32", "rhd_bf16", "hcps4x2_f32", "hcps4x2_bf16",
    "reduce_broadcast_f32", "reduce_broadcast_bf16",
    "gentree_n3", "gentree_n5", "gentree_n6", "gentree_n7",
    "rhd_n3", "rhd_n5", "rhd_n6", "rhd_n7",
    "rs_half", "ag_half",
    "sync_plan", "allreduce_planned", "sync_plan_two_axis",
    "train_plan_finite", "train_plan_matches_psum"])
def test_executed_schedule(results, key):
    assert results[key] is True, (key, results)


def test_hypothesis_sweep_when_available(results):
    if not results["hypothesis_ran"]:
        pytest.skip("hypothesis not installed")
    assert results["hypothesis_sweep"] is True, results.get(
        "hypothesis_error")
