"""Bucketed, double-buffered plan execution — conformance blitz (DESIGN.md §9).

Three layers:

* pure model/partition tests (no devices): dtype-homogeneous size-bounded
  partitioning, the two-stage pipeline time model, and
  `PlannerService.get_bucket_plan` — the chosen bucket size must be the
  GenModel argmin of the sweep, the modeled pipelined time must beat both
  the serial and the per-leaf baselines, schedules must be cached (warm
  hits) and droppable (`invalidate_executables`);
* an 8-host-device subprocess (the test_collectives.py pattern) running
  the differential fuzz: random pytrees — mixed f32/bf16 leaves, scalars,
  odd sizes, empty leaves — synced with bucketed
  `sync_gradients(strategy="plan")` must equal `lax.psum` within dtype
  tolerance (f32 @ 1e-6), on a single axis AND a two-level Table-6-style
  (data × pod) mesh, with auto, pinned-small, unpipelined and disabled
  bucketing;
* `allreduce_planned` bucketing: chunked pipelined execution with stats,
  and the flat-label fallback — it must warn once, record its reason in
  the stats dict, note that the bucketing config was ignored, and still
  match psum.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from _hypothesis_stub import given, settings, strategies as st

from repro.core.bucketing import (BucketConfig, partition, pipelined_time,
                                  serial_time)
from repro.planner.service import PlannerService


# ---------------------------------------------------------------------------
# partition (pure)
# ---------------------------------------------------------------------------
def test_partition_basic_shapes():
    sizes = [5, 0, 3, 100, 1, 7]
    dtypes = ["f32", "f32", "bf16", "f32", "bf16", "f32"]
    bks = partition(sizes, dtypes, 10)
    # every nonzero leaf exactly once, empty leaves in no bucket
    seen = [i for bk in bks for i in bk.indices]
    assert sorted(seen) == [0, 2, 3, 4, 5]
    for bk in bks:
        assert len({str(dtypes[i]) for i in bk.indices}) == 1
        assert bk.size <= 10 or len(bk.indices) == 1   # oversized ride alone
    # deterministic: ordered by first member, order preserved within dtype
    assert bks == partition(sizes, dtypes, 10)
    firsts = [bk.indices[0] for bk in bks]
    assert firsts == sorted(firsts)


@settings(max_examples=50, deadline=None)
@given(leaves=st.lists(st.tuples(st.integers(0, 40),
                                 st.sampled_from(["float32", "bfloat16"])),
                       max_size=30),
       cap=st.integers(1, 64))
def test_partition_properties(leaves, cap):
    sizes = [s for s, _ in leaves]
    dtypes = [d for _, d in leaves]
    bks = partition(sizes, dtypes, cap)
    seen = sorted(i for bk in bks for i in bk.indices)
    assert seen == [i for i, s in enumerate(sizes) if s > 0]
    for bk in bks:
        assert len({str(dtypes[i]) for i in bk.indices}) == 1
        assert bk.size <= cap or len(bk.indices) == 1
        assert bk.sizes == tuple(sizes[i] for i in bk.indices)
        # order-preserving within the bucket
        assert list(bk.indices) == sorted(bk.indices)


def test_partition_byte_cap_spans_dtypes():
    """With itemsizes, ONE byte budget binds every dtype class: under an
    1100 B cap, two 256-element f32 leaves (1024 B each) must split while
    two 256-element bf16 leaves (512 B each) share a bucket — an
    element-only cap would treat them identically."""
    sizes = [256, 256, 256, 256]
    dtypes = ["float32", "float32", "bfloat16", "bfloat16"]
    bks = partition(sizes, dtypes, 1100, itemsizes=[4, 4, 2, 2])
    f32 = [bk.indices for bk in bks if bk.dtype == "float32"]
    bf16 = [bk.indices for bk in bks if bk.dtype == "bfloat16"]
    assert f32 == [(0,), (1,)]      # 2 x 1024 B exceeds the cap
    assert bf16 == [(2, 3)]         # 2 x 512 B fits
    # element-count mode unchanged: all four leaves are 256 elements
    bks_el = partition(sizes, dtypes, 512)
    assert [bk.indices for bk in bks_el] == [(0, 1), (2, 3)]


def test_pipeline_time_model():
    # overlap can never lose; K=1 degenerates to serial
    assert pipelined_time(3.0, 2.0, 1) == serial_time(3.0, 2.0, 1)
    for k in (2, 5, 17):
        assert pipelined_time(3.0, 2.0, k) < serial_time(3.0, 2.0, k)
        assert pipelined_time(3.0, 2.0, k) == 3.0 + (k - 1) * 3.0 + 2.0


# ---------------------------------------------------------------------------
# get_bucket_plan (model only — no devices)
# ---------------------------------------------------------------------------
class TestGetBucketPlan:
    AXES = [("data", 16), ("pod", 4)]
    LEAVES = [50000] * 180 + [1000] * 20

    def test_argmin_and_baselines(self):
        svc = PlannerService()
        bp = svc.get_bucket_plan(self.AXES, 1e7, leaf_sizes=self.LEAVES)
        # the honest rank (DESIGN.md §15): contended pipeline estimate,
        # sandwiched between the optimistic pipeline and serial models
        assert bp.bucket_floats == min(
            bp.sweep, key=lambda b: (bp.sweep[b]["contended"], b))
        assert bp.predicted_pipelined <= bp.predicted_contended
        assert bp.predicted_contended <= bp.predicted_serial + 1e-15
        assert bp.predicted_contended < bp.predicted_per_leaf
        # the sweep explored both directions around the argmin: the trade
        # (α + γ/δ floor vs serialization ceiling) has an interior optimum
        assert len(bp.sweep) > 2
        # one lowered schedule per live axis, sized to the axis
        assert [(p.axis, p.schedule.n) for p in bp.axis_plans] == \
            [("data", 16), ("pod", 4)]

    def test_warm_hit_and_schedule_reuse(self):
        svc = PlannerService()
        b1 = svc.get_bucket_plan(self.AXES, 1e7)
        b2 = svc.get_bucket_plan(self.AXES, 1e7)
        assert b1.source == "cold" and b2.source == "memory"
        # same CompiledSchedule object — cached on the plan entry,
        # never re-lowered per step
        assert b1.axis_plans[0].schedule is b2.axis_plans[0].schedule

    def test_ceiling_bounds_every_candidate(self):
        # a bucket is materialized whole on the device: above the
        # ceiling not even the monolithic candidate is priced
        cfg = BucketConfig(max_bucket_bytes=1 << 22)
        bp = PlannerService().get_bucket_plan(self.AXES, 1e7, config=cfg)
        assert len(bp.sweep) > 1
        assert max(bp.sweep) * 4 <= cfg.max_bucket_bytes
        assert bp.num_buckets > 1

    def test_pinned_bucket_bytes(self):
        svc = PlannerService()
        bp = svc.get_bucket_plan(self.AXES, 1e6,
                                 config=BucketConfig(bucket_bytes=1 << 20))
        assert bp.bucket_floats == (1 << 20) // 4
        assert list(bp.sweep) == [bp.bucket_floats]

    def test_n1_axes_skipped_but_keep_level(self):
        svc = PlannerService()
        bp = svc.get_bucket_plan([("data", 8), ("model", 1)], 1e5)
        assert [a for a, _ in bp.axes] == ["data"]
        bp2 = svc.get_bucket_plan([("model", 1), ("data", 1)], 1e5)
        assert bp2.axes == () and bp2.axis_plans == []

    def test_precision_sweep_and_tolerance_cache(self):
        """Joint (bucket × precision) argmin (DESIGN.md §13): a tolerance
        opens lossy wire candidates, the chosen precision rides the
        sweep rows, and a tolerance change is a cold cache miss — a
        compressed plan is never served to a caller whose error budget
        changed."""
        svc = PlannerService()
        b1 = svc.get_bucket_plan(self.AXES, 1e7,
                                 config=BucketConfig(tolerance=0.3))
        assert b1.source == "cold"
        assert all("precision" in row for row in b1.sweep.values())
        # compression shrinks β·S: on the default params the sweep must
        # pick a lossy wire, and it must price no worse than lossless
        assert b1.precision in ("bf16", "fp8", "int8")
        b_full = svc.get_bucket_plan(self.AXES, 1e7)
        assert b_full.source == "cold" and b_full.precision == "f32"
        assert b1.predicted_pipelined <= b_full.predicted_pipelined
        # warm hit preserves the choice and the wire-bound schedules
        b2 = svc.get_bucket_plan(self.AXES, 1e7,
                                 config=BucketConfig(tolerance=0.3))
        assert b2.source == "memory" and b2.precision == b1.precision
        assert b2.axis_plans[0].schedule is b1.axis_plans[0].schedule
        # tolerance below every lossy budget clamps to lossless — and is
        # its own cache entry (cold), not a stale compressed plan
        b3 = svc.get_bucket_plan(self.AXES, 1e7,
                                 config=BucketConfig(tolerance=0.001))
        assert b3.source == "cold" and b3.precision == "f32"

    def test_precision_pinned_and_clamped(self):
        svc = PlannerService()
        bp = svc.get_bucket_plan(
            self.AXES, 1e6,
            config=BucketConfig(precision="fp8", tolerance=0.3))
        assert bp.precision == "fp8"
        for pl in bp.axis_plans:
            assert pl.schedule.wire is not None
            assert pl.schedule.wire.name == "fp8"
        # a pinned precision whose budget exceeds the tolerance clamps
        # to full precision (resolve_precision), wire stripped
        clamped = svc.get_bucket_plan(
            self.AXES, 1e6,
            config=BucketConfig(precision="fp8", tolerance=0.001))
        assert clamped.precision == "f32"
        assert all(pl.schedule.wire is None for pl in clamped.axis_plans)
        # the wire variant is a distinct object from the f32 user's
        # schedule (guard demotion state must not cross wires)
        assert bp.axis_plans[0].schedule is not \
            clamped.axis_plans[0].schedule

    def test_invalidate_drops_schedules(self):
        svc = PlannerService()
        svc.get_bucket_plan(self.AXES, 1e6)
        assert svc.executable_count() > 0
        dropped = svc.invalidate_executables()
        assert dropped > 0 and svc.executable_count() == 0
        # rebuild is cold for the bucket plan but re-lowers fine
        bp = svc.get_bucket_plan(self.AXES, 1e6)
        assert bp.source == "cold"
        assert all(p.schedule is not None for p in bp.axis_plans)


# ---------------------------------------------------------------------------
# executed conformance on 8 host devices (subprocess)
# ---------------------------------------------------------------------------
_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, warnings
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core import collectives as C
from repro.core.bucketing import BucketConfig
from repro.core.sync import SyncConfig, sync_gradients

results = {}
TOL = {"float32": 1e-6, "bfloat16": 0.05}


def sync_out(tree, axes, mesh_shape, cfg):
    '''The synced tree (and the psum reference) on the sharded mesh.'''
    mesh = make_mesh(mesh_shape, tuple(a for a, _ in reversed(axes)))
    names = tuple(a for a, n in axes if n > 1)
    spec = P(*(a for a, _ in reversed(axes)))
    nlead = len(mesh_shape)

    def local(g):
        return jax.tree.map(lambda v: v[(0,) * nlead], g)

    def lift(g):
        return jax.tree.map(lambda v: v[(None,) * nlead], g)

    f = shard_map(lambda g: lift(sync_gradients(local(g), axes, cfg)),
                  mesh=mesh, in_specs=spec, out_specs=spec)
    p = shard_map(lambda g: lift(jax.tree.map(
        lambda v: jax.lax.psum(v, names), local(g))),
                  mesh=mesh, in_specs=spec, out_specs=spec)
    return jax.jit(f)(tree), jax.jit(p)(tree)


def run_case(tree, axes, mesh_shape, cfg, seed=0, wire_budget=0.0):
    '''Per-leaf max relative error of bucketed sync vs lax.psum,
    normalized to max(dtype tolerance, wire error budget).'''
    got, want = sync_out(tree, axes, mesh_shape, cfg)
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if w.size == 0:
            assert g.size == 0
            continue
        assert g.dtype == w.dtype    # wire compression must not leak out
        tol = max(TOL[str(w.dtype)], wire_budget)
        a = np.asarray(g, np.float64)
        b = np.asarray(w, np.float64)
        err = np.abs(a - b).max() / (np.abs(b).max() + 1e-30)
        worst = max(worst, err / tol)   # normalized to the tolerance
    return worst


def mixed_tree(key, specs):
    leaves = []
    for i, (size, dtype, ndim) in enumerate(specs):
        key, sub = jax.random.split(key)
        shape = () if ndim == 0 else (size,)
        x = jax.random.normal(sub, (8,) + shape, jnp.float32)
        leaves.append(x.astype(dtype))
    return {"leaf%02d" % i: v for i, v in enumerate(leaves)}


FIXED = [(15, jnp.float32, 1), (0, jnp.float32, 1), (1, jnp.float32, 0),
         (129, jnp.bfloat16, 1), (37, jnp.float32, 1),
         (17, jnp.bfloat16, 1), (257, jnp.float32, 1)]
tree = mixed_tree(jax.random.PRNGKey(0), FIXED)

CONFIGS = {
    "auto": SyncConfig(strategy="plan"),
    "small": SyncConfig(strategy="plan", bucket_bytes=256),
    "serial": SyncConfig(strategy="plan", bucket_bytes=256, pipeline=False),
    "off": SyncConfig(strategy="plan", bucket_bytes=0),
}
for name, cfg in CONFIGS.items():
    results[f"fixed_{name}"] = bool(
        run_case(tree, [("x", 8)], (8,), cfg) < 1.0)

# ---- two-level Table-6-style mesh (data x pod) ----------------------------
tree2 = jax.tree.map(lambda v: v.reshape((2, 4) + v.shape[1:]), tree)
for name in ("auto", "small"):
    results[f"table6_{name}"] = bool(run_case(
        tree2, [("data", 4), ("pod", 2)], (2, 4), CONFIGS[name]) < 1.0)

# ---- compressed wire (DESIGN.md §13): plan ≡ psum within the budget -------
from repro.core.cost_model import PRECISIONS
QCASES = {
    "fp8_pin": (SyncConfig(strategy="plan", precision="fp8",
                           tolerance=0.3),
                PRECISIONS["fp8"].error_budget),
    "int8_pin": (SyncConfig(strategy="plan", precision="int8",
                            tolerance=0.3),
                 PRECISIONS["int8"].error_budget),
    "tol_sweep": (SyncConfig(strategy="plan", tolerance=0.3),
                  PRECISIONS["fp8"].error_budget),
    "int8_leaf": (SyncConfig(strategy="plan", bucket_bytes=0,
                             precision="int8", tolerance=0.3),
                  PRECISIONS["int8"].error_budget),
}
for name, (qcfg, budget) in QCASES.items():
    results[f"quant_{name}"] = bool(run_case(
        tree, [("x", 8)], (8,), qcfg, wire_budget=budget) < 1.0)
results["quant_table6_fp8"] = bool(run_case(
    tree2, [("data", 4), ("pod", 2)], (2, 4), QCASES["fp8_pin"][0],
    wire_budget=QCASES["fp8_pin"][1]) < 1.0)

# pinning precision="f32" must be BIT-IDENTICAL to the default planned
# path — the wire machinery is stripped, not run at unit scale
g_plain, _ = sync_out(tree, [("x", 8)], (8,), CONFIGS["auto"])
g_f32, _ = sync_out(tree, [("x", 8)], (8,),
                    SyncConfig(strategy="plan", precision="f32",
                               tolerance=0.5))
results["quant_f32_exact"] = bool(all(
    np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_f32))))

# ---- allreduce_planned: chunked pipelined buckets + stats -----------------
mesh = make_mesh((8,), ("x",))
xa = jnp.arange(8 * 133, dtype=jnp.float32).reshape(8, 133)
stats = {}
f = shard_map(lambda v: C.allreduce_planned(
        v[0], "x", bucketing=BucketConfig(bucket_bytes=128),
        stats=stats)[None],
    mesh=mesh, in_specs=P("x"), out_specs=P("x"))
out = np.asarray(jax.jit(f)(xa))
results["planned_bucketed"] = bool(
    np.allclose(out, np.tile(np.asarray(xa.sum(0)), (8, 1)), rtol=1e-5)
    and stats["mode"] == "bucketed" and stats["num_buckets"] > 1)

# ---- ZeRO-3 bucketed halves on the shard_params_zero3 row layout ----------
from repro.core.bucketing import (zero3_gather_bucketed,
                                  zero3_scatter_bucketed)
from repro.core.sync import AxisPlan
from repro.planner.service import PlannerService
z3 = AxisPlan("x", "plan", schedule=PlannerService().get_axis_executable(
    "x", 8, 4096.0).schedule)
z3_shapes = [(3, 5), (7,), (0,), (2, 33), (1,), (64,)]
z3_leaves = [jax.random.normal(jax.random.PRNGKey(30 + i), s)
             for i, s in enumerate(z3_shapes)]
z3_specs = [(x.shape, x.dtype) for x in z3_leaves]


def z3_rows(x):
    flat = x.reshape(-1)
    flat = jnp.concatenate([flat, jnp.zeros(((-flat.size) % 8,),
                                            flat.dtype)])
    return flat.reshape(8, -1)


for name, cap in (("small", 256), ("whole", 1 << 20)):
    gat = shard_map(
        lambda *s, cap=cap: [v[None] for v in zero3_gather_bucketed(
            [x[0] for x in s], z3_specs, z3, cap, 8)],
        mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    got = jax.jit(gat)(*[z3_rows(x) for x in z3_leaves])
    results[f"zero3_gather_{name}"] = all(
        g.shape == (8,) + x.shape
        and bool(np.array_equal(np.asarray(g),
                                np.broadcast_to(np.asarray(x), g.shape)))
        for g, x in zip(got, z3_leaves))
    sca = shard_map(
        lambda *f, cap=cap: [v[None] for v in zero3_scatter_bucketed(
            [x[0] for x in f], z3, cap, 8)],
        mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    scale = jnp.arange(1.0, 9.0)
    got = jax.jit(sca)(*[scale.reshape((8,) + (1,) * x.ndim) * x[None]
                         for x in z3_leaves])
    results[f"zero3_scatter_{name}"] = all(
        bool(np.allclose(np.asarray(g), np.asarray(z3_rows(36.0 * x)),
                         rtol=1e-6, atol=1e-6))
        for g, x in zip(got, z3_leaves))

# ---- allreduce_planned fallback: warn once + stats record -----------------
from repro.planner.service import PlannerService
from repro.core.sync import level_switch_topo
from repro.core.cost_model import TPU_V5E
svc = PlannerService()
topo = level_switch_topo(8, TPU_V5E, "root_sw")
resp = svc.get_plan(topo, 133 * 4.0, params=TPU_V5E)
resp.plan.num_blocks = None          # legacy / unannotated cache entry
st1, st2 = {}, {}
C._planned_fallback_warned = False
with warnings.catch_warnings(record=True) as wlist:
    warnings.simplefilter("always")
    g = shard_map(lambda v: C.allreduce_planned(
            v[0], "x", service=svc,
            bucketing=BucketConfig(bucket_bytes=128), stats=st1)[None],
        mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    out1 = np.asarray(jax.jit(g)(xa))
    g2 = shard_map(lambda v: C.allreduce_planned(
            v[0], "x", service=svc, stats=st2)[None],
        mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    np.asarray(jax.jit(g2)(xa))
fallback_warns = [w for w in wlist
                  if "flat plan-type labels" in str(w.message)]
results["fallback_correct"] = bool(np.allclose(
    out1, np.tile(np.asarray(xa.sum(0)), (8, 1)), rtol=1e-5))
results["fallback_stats"] = bool(
    st1["mode"] == "flat-label" and "no block annotations" in
    st1["fallback_reason"] and st1["bucketing_ignored"] is True
    and st2["mode"] == "flat-label"
    and st2["bucketing_ignored"] is False)
results["fallback_warns_once"] = len(fallback_warns) == 1

# ---- hypothesis differential fuzz (runs when hypothesis is installed) -----
try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYP = True
except ImportError:
    HAVE_HYP = False
results["hypothesis_ran"] = HAVE_HYP
if HAVE_HYP:
    leaf_spec = hst.tuples(hst.integers(0, 64),
                           hst.sampled_from([jnp.float32, jnp.bfloat16]),
                           hst.integers(0, 1))

    @settings(max_examples=10, deadline=None)
    @given(specs=hst.lists(leaf_spec, min_size=1, max_size=8),
           bucket=hst.sampled_from([None, 128, 512]),
           pipeline=hst.booleans(),
           two_level=hst.booleans(),
           seed=hst.integers(0, 10 ** 6))
    def fuzz(specs, bucket, pipeline, two_level, seed):
        cfg = SyncConfig(strategy="plan", bucket_bytes=bucket,
                         pipeline=pipeline)
        t = mixed_tree(jax.random.PRNGKey(seed), specs)
        if two_level:
            t = jax.tree.map(
                lambda v: v.reshape((2, 4) + v.shape[1:]), t)
            worst = run_case(t, [("data", 4), ("pod", 2)], (2, 4), cfg)
        else:
            worst = run_case(t, [("x", 8)], (8,), cfg)
        assert worst < 1.0, (specs, bucket, pipeline, two_level, worst)

    try:
        fuzz()
        results["hypothesis_fuzz"] = True
    except Exception as e:
        results["hypothesis_fuzz"] = False
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
    "fixed_auto", "fixed_small", "fixed_serial", "fixed_off",
    "table6_auto", "table6_small",
    "quant_fp8_pin", "quant_int8_pin", "quant_tol_sweep",
    "quant_int8_leaf", "quant_table6_fp8", "quant_f32_exact",
    "planned_bucketed",
    "zero3_gather_small", "zero3_gather_whole",
    "zero3_scatter_small", "zero3_scatter_whole",
    "fallback_correct", "fallback_stats", "fallback_warns_once"])
def test_bucketed_sync(results, key):
    assert results[key] is True, (key, results)


def test_hypothesis_fuzz_when_available(results):
    if not results["hypothesis_ran"]:
        pytest.skip("hypothesis not installed")
    assert results["hypothesis_fuzz"] is True, results.get(
        "hypothesis_error")
