"""Per-family executed schedules ≡ lax references on a real 8-device mesh.

The acceptance bar for the multi-family Plan IR (DESIGN.md §14): every
lowered family schedule — AllGather / ReduceScatter halves of the axis's
GenTree AllReduce plan, the flat AllToAll exchange, and the P2P shift —
must match its `lax` reference (`all_gather` / `psum_scatter` /
`all_to_all` / `ppermute`) within 1e-6 on 8 host CPU devices, including
the Table-6 two-level mesh; the strategy-dispatch round-trip
`collectives.all_gather(collectives.reduce_scatter(x, s), s)` must equal
psum for every strategy on non-power-of-two axes and non-aligned sizes
(the hcps shard-order bug this PR fixes); and the expert-parallel MoE
dispatch (`moe_dispatch="ep"`) must match the single-device sorted block
both over `lax.all_to_all` and over a planner-lowered AllToAll schedule,
with `deepseek_moe_16b` training end to end under `sync="plan"`.

Like test_exec_equivalence.py, one subprocess (XLA_FLAGS device-count=8)
runs every multi-device case and prints one RESULTS json line; the
hypothesis sweep rides in the same subprocess when installed. Plain
single-process tests at the bottom pin `get_step_plan`'s
pricing-consistency invariant (Σ family terms ≡ joint quote at 1e-9) and
the per-call-dominance ratio ≤ 1.
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
from repro.core import collectives, topology
from repro.core import sync as sync_mod
from repro.core.gentree import family_plan, gentree
from repro.core.lower import lower_plan
from repro.core.plans import family_halves
from repro.planner.service import PlannerService

results = {}


def mesh_of(n):
    return make_mesh((n,), ("x",), devices=jax.devices()[:n])


def rand(n, size, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, size),
                             jnp.float32).astype(dtype)


def relerr(got, want):
    got = np.asarray(got).astype(np.float64)
    want = np.asarray(want).astype(np.float64)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def run_pair(n, f_got, f_want, size, seed=0):
    x = rand(n, size, seed)
    m = mesh_of(n)
    g = shard_map(lambda v: f_got(v[0])[None], mesh=m,
                  in_specs=P("x"), out_specs=P("x"))
    w = shard_map(lambda v: f_want(v[0])[None], mesh=m,
                  in_specs=P("x"), out_specs=P("x"))
    return relerr(jax.jit(g)(x), jax.jit(w)(x))


# ---- planned family schedules vs lax references ---------------------------
# Schedules from both a flat single-switch mesh and the Table-6-style
# two-level tree (2 middle switches x 4 servers) — the lowered halves of
# a multi-level GenTree plan must keep the same device<->shard contract.
TOPOS = {"ss8": topology.single_switch(8),
         "table6": topology.symmetric_tree(2, 4)}
for tname, topo in TOPOS.items():
    size = 1024
    ag = lower_plan(family_plan("allgather", topo, float(size)))
    rs = lower_plan(family_plan("reduce_scatter", topo, float(size)))
    err = run_pair(
        8, lambda v: ag.all_gather(v, "x"),
        lambda v: jax.lax.all_gather(v, "x", axis=0, tiled=True), size // 8)
    results[f"ag_{tname}_err"] = err
    results[f"ag_{tname}"] = err < 1e-6
    err = run_pair(
        8, lambda v: rs.reduce_scatter(v, "x"),
        lambda v: jax.lax.psum_scatter(v, "x", scatter_dimension=0,
                                       tiled=True), size)
    results[f"rs_{tname}_err"] = err
    results[f"rs_{tname}"] = err < 1e-6

a2a = lower_plan(family_plan("all_to_all", TOPOS["ss8"], 4096.0))
err = run_pair(
    8, lambda v: a2a.all_to_all(v, "x"),
    lambda v: jax.lax.all_to_all(v.reshape((8, -1)), "x", split_axis=0,
                                 concat_axis=0).reshape(v.shape), 64)
results["a2a_err"] = err
results["a2a"] = err < 1e-6

p2p = lower_plan(family_plan("p2p", TOPOS["ss8"], 512.0))
err = run_pair(
    8, lambda v: p2p.p2p(v, "x"),
    lambda v: jax.lax.ppermute(v, "x",
                               [(i, (i + 1) % 8) for i in range(8)]), 64)
results["p2p_err"] = err
results["p2p"] = err < 1e-6


# ---- strategy round-trips: all_gather(reduce_scatter(x)) == psum ----------
# Non-power-of-two axes and non-aligned sizes exercise the zero-pad path;
# hcps exercises the digit-reversed shard-order un-reorder in the
# all_gather dispatch (calling all_gather_hcps directly on the reordered
# reduce_scatter shard block-permutes the vector — the bug this PR fixes).
def roundtrip(n, strategy, size, factors=None, seed=3):
    def got(v):
        flat = v.reshape(-1)
        pad = (-flat.size) % collectives._pad_multiple(n, strategy)
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        shard = collectives.reduce_scatter(flat, "x", strategy,
                                           factors=factors)
        full = collectives.all_gather(shard, "x", strategy, factors=factors)
        if pad:
            full = full[:-pad]
        return full.reshape(v.shape)
    return run_pair(n, got, lambda v: jax.lax.psum(v, "x"), size, seed=seed)

for n, size, label in [(8, 37, "n8_s37"), (6, 37, "n6_s37"), (6, 96, "n6")]:
    for strat in ("psum", "ring", "cps", "rhd"):
        err = roundtrip(n, strat, size)
        results[f"rt_{strat}_{label}_err"] = err
        results[f"rt_{strat}_{label}"] = err < 1e-6
err = roundtrip(8, "hcps", 37, factors=[2, 2, 2])
results["rt_hcps_n8_s37_err"] = err
results["rt_hcps_n8_s37"] = err < 1e-6
err = roundtrip(6, "hcps", 37, factors=[2, 3])
results["rt_hcps_n6_s37_err"] = err
results["rt_hcps_n6_s37"] = err < 1e-6

# the un-reorder is load-bearing: the raw hcps doubling phase on the
# natural-order shard must NOT reproduce psum (factors [2,2,2] digit
# reversal swaps shards 1<->4 and 3<->6)
def hcps_raw(v):
    shard = collectives.reduce_scatter(v.reshape(-1), "x", "hcps",
                                       factors=[2, 2, 2])
    return collectives.all_gather_hcps(shard, "x", [2, 2, 2]).reshape(v.shape)
results["hcps_raw_misorders"] = run_pair(
    8, hcps_raw, lambda v: jax.lax.psum(v, "x"), 64, seed=5) > 1e-3


# ---- expert-parallel MoE dispatch == sorted reference block ---------------
from repro.models import layers

def moe_case(sched):
    n, E, k, D, ntok = 8, 8, 2, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    p = {"wi": jax.random.normal(ks[0], (E, D, 24), jnp.float32) * 0.1,
         "wg": jax.random.normal(ks[1], (E, D, 24), jnp.float32) * 0.1,
         "wo": jax.random.normal(ks[2], (E, 24, D), jnp.float32) * 0.1}
    xt = jax.random.normal(ks[3], (n, ntok, D), jnp.float32)
    logits = jax.random.normal(ks[4], (n, ntok, E), jnp.float32)
    topv, topi = jax.lax.top_k(jax.nn.softmax(logits), k)
    topv = topv / topv.sum(-1, keepdims=True)
    m = mesh_of(n)
    ref = shard_map(
        lambda x, ti, tv: layers._moe_sorted_block(
            x[0], ti[0], tv[0], p, E, k, D, 1.25)[None],
        mesh=m, in_specs=(P("x"),) * 3, out_specs=P("x"))
    want = jax.jit(ref)(xt, topi, topv)
    with sync_mod.expert_parallel("x", n, sched):
        ep = shard_map(
            lambda x, ti, tv: layers._moe_ep(
                p, x[0], ti[0], tv[0], None, E, k, D, 1.25)[None],
            mesh=m, in_specs=(P("x"),) * 3, out_specs=P("x"))
        got = jax.jit(ep)(xt, topi, topv)
    return relerr(got, want)

err = moe_case(None)
results["moe_ep_lax_err"] = err
results["moe_ep_lax"] = err < 1e-6
svc = PlannerService()
sched = svc.get_family_executable("all_to_all", "x", 8, 4096.0).schedule
results["moe_ep_sched_lowered"] = sched is not None
err = moe_case(sched)
results["moe_ep_plan_err"] = err
results["moe_ep_plan"] = err < 1e-6


# ---- acceptance: deepseek_moe_16b trains under sync="plan" with EP --------
from repro.launch.train import run_training, TrainConfig

ep_calls = [0]
_orig_moe_ep = layers._moe_ep
def _counting_moe_ep(*a, **kw):
    ep_calls[0] += 1
    return _orig_moe_ep(*a, **kw)
layers._moe_ep = _counting_moe_ep
try:
    res_plan = run_training(TrainConfig(arch="deepseek_moe_16b", steps=2,
                                        engine="manual", sync="plan",
                                        seq_len=16, global_batch=8))
finally:
    layers._moe_ep = _orig_moe_ep
res_psum = run_training(TrainConfig(arch="deepseek_moe_16b", steps=2,
                                    engine="manual", sync="psum",
                                    seq_len=16, global_batch=8))
lp = [float(x) for x in res_plan["losses"]]
ls = [float(x) for x in res_psum["losses"]]
results["train_moe_plan_finite"] = bool(np.all(np.isfinite(lp)))
results["train_moe_ep_dispatch_used"] = ep_calls[0] > 0
dl = max(abs(a - b) for a, b in zip(lp, ls))
results["train_moe_loss_delta"] = dl
results["train_moe_plan_matches_psum"] = bool(dl < 1e-3)


# ---- hypothesis sweep (CI; skipped when hypothesis is absent) -------------
try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYP = True
except ImportError:
    HAVE_HYP = False
results["hypothesis_ran"] = HAVE_HYP
if HAVE_HYP:
    @settings(max_examples=10, deadline=None)
    @given(family=hst.sampled_from(["allgather", "reduce_scatter",
                                    "all_to_all", "p2p"]),
           tname=hst.sampled_from(["ss8", "table6"]),
           chunk=hst.integers(1, 40), seed=hst.integers(0, 10**6))
    def fam_sweep(family, tname, chunk, seed):
        cs = lower_plan(family_plan(family, TOPOS[tname], float(8 * chunk)))
        if family == "allgather":
            err = run_pair(8, lambda v: cs.all_gather(v, "x"),
                           lambda v: jax.lax.all_gather(
                               v, "x", axis=0, tiled=True), chunk, seed=seed)
        elif family == "reduce_scatter":
            err = run_pair(8, lambda v: cs.reduce_scatter(v, "x"),
                           lambda v: jax.lax.psum_scatter(
                               v, "x", scatter_dimension=0, tiled=True),
                           8 * chunk, seed=seed)
        elif family == "all_to_all":
            err = run_pair(8, lambda v: cs.all_to_all(v, "x"),
                           lambda v: jax.lax.all_to_all(
                               v.reshape((8, -1)), "x", split_axis=0,
                               concat_axis=0).reshape(v.shape),
                           8 * chunk, seed=seed)
        else:
            err = run_pair(8, lambda v: cs.p2p(v, "x"),
                           lambda v: jax.lax.ppermute(
                               v, "x", [(i, (i + 1) % 8) for i in range(8)]),
                           chunk, seed=seed)
        assert err < 1e-6, (family, tname, chunk, err)

    @settings(max_examples=10, deadline=None)
    @given(n=hst.sampled_from([5, 6, 7, 8]),
           strat=hst.sampled_from(["psum", "ring", "cps", "rhd"]),
           size=hst.integers(1, 200), seed=hst.integers(0, 10**6))
    def rt_sweep(n, strat, size, seed):
        err = roundtrip(n, strat, size, seed=seed)
        assert err < 1e-6, (n, strat, size, err)

    try:
        fam_sweep()
        rt_sweep()
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
                         capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


@pytest.mark.parametrize("key", [
    "ag_ss8", "rs_ss8", "ag_table6", "rs_table6", "a2a", "p2p",
    "rt_psum_n8_s37", "rt_ring_n8_s37", "rt_cps_n8_s37", "rt_rhd_n8_s37",
    "rt_psum_n6_s37", "rt_ring_n6_s37", "rt_cps_n6_s37", "rt_rhd_n6_s37",
    "rt_psum_n6", "rt_ring_n6", "rt_cps_n6", "rt_rhd_n6",
    "rt_hcps_n8_s37", "rt_hcps_n6_s37", "hcps_raw_misorders",
    "moe_ep_lax", "moe_ep_sched_lowered", "moe_ep_plan",
    "train_moe_plan_finite", "train_moe_ep_dispatch_used",
    "train_moe_plan_matches_psum"])
def test_family_schedules(results, key):
    assert results[key] is True, (key, results)


def test_hypothesis_sweep_when_available(results):
    if not results["hypothesis_ran"]:
        pytest.skip("hypothesis not installed")
    assert results["hypothesis_sweep"] is True, results.get(
        "hypothesis_error")


# ---- single-process: whole-step pricing consistency -----------------------
MIX = {"allreduce": {"count": 4, "size_floats": 1 << 20},
       "reduce_scatter": {"count": 2, "size_floats": 1 << 18},
       "allgather": {"count": 2, "size_floats": 1 << 18},
       "all_to_all": {"count": 6, "size_floats": 1 << 16},
       "p2p": {"count": 1, "size_floats": 1 << 14}}


def _service():
    from repro.planner.service import PlannerService
    return PlannerService()


def test_step_plan_pricing_consistency():
    """Σ per-family joint terms must equal the joint total exactly (1e-9)
    — the StepPlan invariant DESIGN.md §14 documents."""
    svc = _service()
    sp = svc.get_step_plan([("data", 8)], MIX)
    total = 0.0
    for fam, q in sp.quotes.items():
        assert q["joint"], fam
        fam_total = sum(q["joint"].values())
        assert abs(fam_total - q["joint_total"]) <= \
            1e-9 * max(1.0, q["joint_total"]), (fam, fam_total, q)
        total += fam_total
    assert abs(total - sp.total_joint) <= 1e-9 * max(1.0, sp.total_joint)


def test_step_plan_ratio_bounded():
    """Joint planning may never lose to naïve per-call planning — the
    per-call regime is in the argmin, so ratio ≤ 1 by construction."""
    svc = _service()
    sp = svc.get_step_plan([("data", 8)], MIX)
    assert 0.0 < sp.ratio <= 1.0 + 1e-12, sp.ratio
    assert sp.total_best <= sp.total_per_call * (1 + 1e-12)
    for fam in MIX:
        assert fam in sp.schedules, fam


def test_step_plan_from_module_stats():
    """A ModuleStats census (the analyze_hlo output) prices through the
    same path as an explicit mix spec."""
    from repro.launch.hlo_analysis import ModuleStats
    stats = ModuleStats()
    stats.add_coll("all-reduce", 2.0 * 4096, payload=4096.0)
    stats.add_coll("all-to-all", 0.875 * 2048, payload=2048.0)
    svc = _service()
    sp = svc.get_step_plan([("data", 8)], stats)
    assert set(sp.quotes) == {"allreduce", "all_to_all"}
    assert sp.quotes["allreduce"]["count"] == 1
