"""Cross-family overlap scheduling (DESIGN.md §15).

Three layers, mirroring the subsystem:

* Pricing — `FastEngine.contended_halves_total` (vectorized occupancy
  merge) must agree with the reference `cost_model.contended_pair_time`
  walk at 1e-9 on every topology class, sit inside the
  [max, adversarial] envelope, and the `contended_pipelined_time` /
  `overlap_certificate` algebra must clamp and sandwich correctly.
* Merging — `plan_merge` validates the cross-schedule contract,
  `MergedSchedule.run_numpy_pair` must be numerically identical to the
  sequential constituents under EVERY order-preserving interleaving
  (hypothesis sweep over shuffled token streams).
* Planning — `PlannerService.get_bucket_plan` may select merged
  issuance ONLY when the contended price beats sequential
  (planner-never-selects-a-losing-merge), and must still select it
  somewhere (both modes are live, not a constant fallback).

The 8-device differential (merged rs_ag ≡ sequential RS+AG ≡ lax
references at 1e-6 on the Table-6 two-level mesh, plus the int8
wire-compressed variant) runs in one subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8, like
test_exec_equivalence.py.
"""
import json
import os
import random
import subprocess
import sys

import numpy as np
import pytest

from _hypothesis_stub import given, settings, strategies as st

from repro.core import topology
from repro.core.bucketing import (BucketConfig, contended_pipelined_time,
                                  pipelined_time, serial_time)
from repro.core.cost_model import contended_pair_time
from repro.core.gentree import gentree
from repro.core.lower import LoweringError, lower_plan
from repro.core.optimality import (overlap_certificate,
                                   overlap_lower_bound,
                                   overlap_upper_bound)
from repro.core.overlap import (merge_schedules, occupancy_summary,
                                plan_merge, rounds_link_disjoint)
from repro.core.plans import family_halves
from repro.core.simfast import FastEngine

TOPOS = {
    "ss8": lambda: topology.single_switch(8),
    "tree8": lambda: topology.symmetric_tree(2, 4),
    "cdc16": lambda: topology.cross_dc(dc0_middle=2, dc0_servers=4,
                                       dc1_middle=2, dc1_servers=4),
}


# ---------------------------------------------------------------------------
# Pricing: engine agreement + envelope
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(TOPOS))
def test_fast_engine_matches_reference_contended(name):
    topo = TOPOS[name]()
    plan = gentree(topo, 1e6).plan
    rs_half, ag_half = family_halves(plan)
    fast = FastEngine(topo).contended_halves_total(rs_half, ag_half)
    ref = contended_pair_time(topo, rs_half, ag_half)
    assert abs(fast - ref) / max(1e-30, ref) <= 1e-9, (name, fast, ref)


@pytest.mark.parametrize("name", sorted(TOPOS))
def test_contended_pair_envelope(name):
    """A concurrent pair can never beat max(t_rs, t_ag) — the busiest
    link still carries the slower half's units — and the pipeline model
    clamps it to at most sequential issuance."""
    topo = TOPOS[name]()
    plan = gentree(topo, 1e6).plan
    rs_half, ag_half = family_halves(plan)
    eng = FastEngine(topo)
    t_rs, t_ag = eng.halves_totals(plan)
    t_joint = eng.contended_halves_total(rs_half, ag_half)
    assert t_joint >= max(t_rs, t_ag) - 1e-15, (name, t_joint, t_rs, t_ag)
    k = 8
    piped = contended_pipelined_time(t_rs, t_ag, k, t_joint)
    assert overlap_lower_bound(t_rs, t_ag, k) <= piped + 1e-15
    assert piped <= overlap_upper_bound(t_rs, t_ag, k) + 1e-15


def test_contended_pipelined_time_edges():
    assert contended_pipelined_time(1.0, 2.0, 0) == 0.0
    assert contended_pipelined_time(1.0, 2.0, -3) == 0.0
    # one bucket: no steady state, halves run back to back
    assert contended_pipelined_time(1.0, 2.0, 1, 99.0) == 3.0
    # default joint = optimistic max  ->  reduces to pipelined_time
    assert contended_pipelined_time(1.0, 2.0, 5) == \
        pipelined_time(1.0, 2.0, 5)
    # joint below max clamps UP to max (can't beat the slower half)
    assert contended_pipelined_time(1.0, 2.0, 5, 0.5) == \
        pipelined_time(1.0, 2.0, 5)
    # joint above sum clamps DOWN to sequential issuance
    assert contended_pipelined_time(1.0, 2.0, 5, 10.0) == \
        serial_time(1.0, 2.0, 5)
    # interior joint lands between the bounds
    mid = contended_pipelined_time(1.0, 2.0, 5, 2.5)
    assert pipelined_time(1.0, 2.0, 5) < mid < serial_time(1.0, 2.0, 5)


def test_overlap_certificate_sandwich():
    for tj in (2.0, 2.5, 3.0):
        quoted = contended_pipelined_time(1.0, 2.0, 4, tj)
        cert = overlap_certificate(1.0, 2.0, 4, quoted)
        assert cert["sandwiched"], cert
        assert cert["lower_bound"] <= cert["quoted"] <= cert["upper_bound"]
        assert 0.0 <= cert["gap_ratio"] <= 1.0 + 1e-12
    # a quote outside the envelope is rejected
    assert not overlap_certificate(1.0, 2.0, 4, 0.5)["sandwiched"]
    assert not overlap_certificate(
        1.0, 2.0, 4, serial_time(1.0, 2.0, 4) * 2)["sandwiched"]


def test_occupancy_summary_self_overlap():
    topo = TOPOS["tree8"]()
    plan = gentree(topo, 1e6).plan
    rs_half, ag_half = family_halves(plan)
    summ = occupancy_summary(topo, rs_half.steps[0], ag_half.steps[0])
    assert summ["links_rs"] > 0 and summ["links_ag"] > 0
    assert 0 <= summ["links_shared"] <= min(summ["links_rs"],
                                            summ["links_ag"])
    assert summ["busiest_link_units"] > 0.0


# ---------------------------------------------------------------------------
# Merging: contract + numpy differential + interleaving sweep
# ---------------------------------------------------------------------------
def _self_merge(n=8, size=1e5):
    cs = lower_plan(gentree(topology.single_switch(n), size).plan)
    return cs, merge_schedules(cs, cs)


def test_plan_merge_self_is_valid_but_fully_serialized():
    cs, ms = _self_merge()
    info = ms.info
    assert info.n == 8
    assert info.round_pairs > 0
    # a schedule merged with itself shares every link every round
    assert info.coalesced == 0
    assert info.serialized == info.round_pairs
    assert 0.0 <= info.coalesced_fraction <= 1.0


def test_merge_schedules_memoized():
    cs, ms = _self_merge()
    assert merge_schedules(cs, cs) is ms


def test_plan_merge_rejects_family_and_size_mismatch():
    plan = gentree(topology.single_switch(8), 1e5).plan
    rs_half, ag_half = family_halves(plan)
    rs_cs, ag_cs = lower_plan(rs_half), lower_plan(ag_half)
    # AG-family schedule on the RS side of the merge
    with pytest.raises(LoweringError):
        plan_merge(ag_cs, ag_cs)
    # RS-family schedule on the AG side
    with pytest.raises(LoweringError):
        plan_merge(rs_cs, rs_cs)
    # axis-size mismatch
    other = lower_plan(gentree(topology.single_switch(4), 1e5).plan)
    with pytest.raises(LoweringError):
        plan_merge(lower_plan(plan), other)
    # the valid direction works
    info = plan_merge(rs_cs, ag_cs)
    assert info.round_pairs >= 0


def test_rounds_link_disjoint():
    cs = lower_plan(gentree(topology.single_switch(8), 1e5).plan)
    rd = cs.rs[0].rounds[0]
    # a round shares every link with itself
    assert not rounds_link_disjoint(rd, rd)


def _numpy_pair_expected(ms, X, shards):
    """Closed-form references for run_numpy_pair on canonical layouts."""
    a, b = ms.rs_inner, ms.ag_inner
    n = ms.n
    tot = X.sum(axis=0)
    pad = (-X.shape[1]) % a.num_blocks
    tot = np.concatenate([tot, np.zeros(pad, X.dtype)])
    chunk = tot.size // a.num_blocks
    ka = a.blocks_per_shard
    rs_want = np.stack([
        tot.reshape(a.num_blocks, chunk)[d * ka:(d + 1) * ka].reshape(-1)
        for d in range(n)])
    ag_row = np.concatenate([shards[d] for d in range(n)])
    ag_want = np.tile(ag_row, (n, 1))
    return rs_want, ag_want


def test_run_numpy_pair_matches_closed_form():
    cs, ms = _self_merge(n=8, size=1e5)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(8, 173)).astype(np.float64)
    shards = rng.normal(
        size=(8, ms.ag_inner.blocks_per_shard * 5)).astype(np.float64)
    rs_out, ag_out = ms.run_numpy_pair(X, shards)
    rs_want, ag_want = _numpy_pair_expected(ms, X, shards)
    np.testing.assert_allclose(rs_out, rs_want, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(ag_out, ag_want, rtol=1e-12, atol=1e-12)


def test_run_numpy_pair_rejects_bad_order():
    cs, ms = _self_merge(n=4, size=1e4)
    X = np.ones((4, 32))
    shards = np.ones((4, ms.ag_inner.blocks_per_shard * 2))
    with pytest.raises(LoweringError):
        ms.run_numpy_pair(X, shards, order=["a"])  # token counts off
    with pytest.raises(LoweringError):
        ms.run_numpy_pair(X[:3], shards)           # wrong device count


@settings(max_examples=20, deadline=None)
@given(n=st.sampled_from([4, 8]), size=st.integers(1, 200),
       chunks=st.integers(1, 4), seed=st.integers(0, 10**6))
def test_run_numpy_pair_interleaving_invariant(n, size, chunks, seed):
    """ANY interleaving that preserves each constituent's internal step
    order produces bit-identical outputs — the disjoint-buffer fact the
    merged executor leans on."""
    cs = lower_plan(gentree(topology.single_switch(n), 1e4).plan)
    ms = merge_schedules(cs, cs)
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, size)).astype(np.float64)
    shards = rng.normal(
        size=(n, ms.ag_inner.blocks_per_shard * chunks)).astype(np.float64)
    rs_ref, ag_ref = ms.run_numpy_pair(X, shards)

    from repro.core.overlap import _ag_steps, _rs_steps
    toks = (["a"] * len(_rs_steps(ms.rs_inner))
            + ["b"] * len(_ag_steps(ms.ag_inner)))
    shuf = random.Random(seed)
    for _ in range(3):
        shuf.shuffle(toks)
        rs_out, ag_out = ms.run_numpy_pair(X, shards, order=toks)
        assert np.array_equal(rs_out, rs_ref), (n, size, toks)
        assert np.array_equal(ag_out, ag_ref), (n, size, toks)


# ---------------------------------------------------------------------------
# Planning: the argmin may only pick a winning merge
# ---------------------------------------------------------------------------
def test_planner_never_selects_losing_merge():
    from repro.planner.service import PlannerService
    svc = PlannerService()
    modes = set()
    for n in (4, 8, 16):
        for bb in (1 << 18, 1 << 20, 1 << 22, 1 << 23):
            bp = svc.get_bucket_plan([("data", n)], 4_000_000.0,
                                     config=BucketConfig(bucket_bytes=bb))
            ov = bp.overlap
            assert ov["mode"] in ("merged", "sequential"), ov
            modes.add(ov["mode"])
            t_seq = ov["t_pair_sequential"]
            if ov["mode"] == "merged":
                # a selected merge must strictly beat sequential issuance
                assert bp.num_buckets > 1
                assert 0.0 < ov["t_joint"] < t_seq, (n, bb, ov)
                assert bp.merged_schedule is not None, (n, bb)
            else:
                # sequential ⇔ no strict win was available
                assert (bp.num_buckets <= 1
                        or not ov["t_joint"]
                        or ov["t_joint"] >= t_seq), (n, bb, ov)
                assert bp.merged_schedule is None, (n, bb)
            # either way the quoted contended time respects the sandwich
            assert bp.predicted_pipelined <= bp.predicted_contended + 1e-15
            assert bp.predicted_contended <= bp.predicted_serial + 1e-15
    # both decisions must be exercised by the scan — a planner that
    # always answers "sequential" (or always "merged") is broken
    assert modes == {"merged", "sequential"}, modes


def test_step_plan_quotes_contended_with_certificate():
    from repro.planner.service import PlannerService
    svc = PlannerService()
    sp = svc.get_step_plan(
        [("data", 8)],
        {"allreduce": {"count": 4, "size_floats": 1 << 20},
         "allgather": {"count": 2, "size_floats": 1 << 18}})
    certs = 0
    for fam, quote in sp.quotes.items():
        if quote.get("certificate") is None:
            continue
        certs += 1
        cert = quote["certificate"]
        assert cert["sandwiched"], (fam, cert)
        assert quote["pipelined"] <= quote["contended"] + 1e-15, (fam,
                                                                  quote)
    # the multi-call allreduce family must carry a §15 certificate
    assert certs >= 1, sp.quotes


# ---------------------------------------------------------------------------
# 8-device differential: merged ≡ sequential ≡ lax at 1e-6
# ---------------------------------------------------------------------------
_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core import topology
from repro.core.cost_model import PRECISIONS
from repro.core.gentree import gentree
from repro.core.lower import lower_plan
from repro.core.overlap import merge_schedules

results = {}
N, SIZE = 8, 173
mesh = make_mesh((N,), ("x",), devices=jax.devices()[:N])
rng = np.random.default_rng(7)


def launch(fn, *xs):
    f = shard_map(lambda *vs: [o[None] for o in fn(*[v[0] for v in vs])],
                  mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    return [np.asarray(o).astype(np.float64) for o in jax.jit(f)(*xs)]


def relerr(got, want):
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


# Table-6 two-level mesh: the acceptance topology
cs = lower_plan(gentree(topology.symmetric_tree(2, 4), 1e6).plan)
ms = merge_schedules(cs, cs)
kb = ms.ag_inner.blocks_per_shard
X = jnp.asarray(rng.normal(size=(N, SIZE)), jnp.float32)
S = jnp.asarray(rng.normal(size=(N, kb * 3)), jnp.float32)

# merged issuance
m_shard, m_full = launch(
    lambda x, s: ms.rs_ag(x, s, "x"), X, S)
results["merged_demoted_after"] = bool(ms.demoted)
# sequential issuance through the raw constituents
s_shard, s_full = launch(
    lambda x, s: (cs.reduce_scatter(x, "x"), cs.all_gather(s, "x")), X, S)
results["merged_vs_sequential_shard"] = relerr(m_shard, s_shard)
results["merged_vs_sequential_full"] = relerr(m_full, s_full)

# lax references: RS shard == slice of psum; AG full == all device shards
Xn = np.asarray(X, np.float64)
tot = Xn.sum(0)
pad = (-SIZE) % ms.rs_inner.num_blocks
tot = np.concatenate([tot, np.zeros(pad)])
chunk = tot.size // ms.rs_inner.num_blocks
ka = ms.rs_inner.blocks_per_shard
rs_want = np.stack([
    tot.reshape(-1, chunk)[d * ka:(d + 1) * ka].reshape(-1)
    for d in range(N)])
ag_want = np.tile(np.asarray(S, np.float64).reshape(-1), (N, 1))
results["merged_vs_lax_shard"] = relerr(m_shard, rs_want)
results["merged_vs_lax_full"] = relerr(m_full, ag_want)

# demoted wrapper serves the same values through the sequential rung
ms._demoted = True
d_shard, d_full = launch(lambda x, s: ms.rs_ag(x, s, "x"), X, S)
results["demoted_vs_merged_shard"] = relerr(d_shard, m_shard)
results["demoted_vs_merged_full"] = relerr(d_full, m_full)
ms.reset_guard()

# int8 wire-compressed constituents: merged interleaves at step
# granularity through the constituents' own wire machinery, so the
# merged and sequential compressed paths must agree bit-for-bit-close
cs8 = cs.with_wire(PRECISIONS["int8"])
ms8 = merge_schedules(cs8, cs8)
m8_shard, m8_full = launch(lambda x, s: ms8.rs_ag(x, s, "x"), X, S)
s8_shard, s8_full = launch(
    lambda x, s: (cs8.reduce_scatter(x, "x"), cs8.all_gather(s, "x")),
    X, S)
results["compressed_merged_vs_sequential_shard"] = relerr(m8_shard,
                                                          s8_shard)
results["compressed_merged_vs_sequential_full"] = relerr(m8_full, s8_full)
# quantized-vs-exact stays inside the int8 error budget
results["compressed_vs_lax_shard"] = relerr(m8_shard, rs_want)
results["compressed_budget"] = float(PRECISIONS["int8"].error_budget)

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
    "merged_vs_sequential_shard", "merged_vs_sequential_full",
    "merged_vs_lax_shard", "merged_vs_lax_full",
    "demoted_vs_merged_shard", "demoted_vs_merged_full",
    "compressed_merged_vs_sequential_shard",
    "compressed_merged_vs_sequential_full"])
def test_eight_device_differential(results, key):
    assert results[key] < 1e-6, (key, results)


def test_eight_device_merged_not_demoted(results):
    assert results["merged_demoted_after"] is False, results


def test_eight_device_compressed_within_budget(results):
    assert results["compressed_vs_lax_shard"] < \
        results["compressed_budget"], results
