"""Chaos hardening (DESIGN.md §12): deterministic fault injection,
guarded schedule execution, degraded-mode replanning, refit guardrails,
and corruption-tolerant cache/checkpoint loading.

The unit tests run single-process and jax-light; the chaos soak runs an
8-device training differential in a subprocess: a run under an armed
FaultPlan (device loss, link sag, checkpoint corruption) must land on
the same final parameters as the fault-free run.
"""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest
from _hypothesis_stub import given, settings, strategies as st

from repro.runtime.faults import (ENV_VAR, FaultEvent, FaultInjector,
                                  FaultPlan, InjectedFault, active_injector)


@pytest.fixture
def quiet_faults(monkeypatch):
    """Deterministic fault environment: mask any ambient injector (the
    CI chaos job arms $REPRO_FAULT_PLAN for the whole suite) with an
    empty scoped plan, so guard/ladder assertions see exactly the events
    each test arms itself."""
    monkeypatch.delenv(ENV_VAR, raising=False)
    with FaultInjector(FaultPlan()) as inj:
        yield inj


# ---------------------------------------------------------------------------
# FaultPlan: determinism + parsing
# ---------------------------------------------------------------------------
def test_generate_is_deterministic():
    kw = dict(device_loss=0.05, link_degrade=0.05, delay=0.1,
              payload_corrupt=0.1, file_corrupt=0.05)
    a = FaultPlan.generate(7, 200, **kw)
    b = FaultPlan.generate(7, 200, **kw)
    assert a.events == b.events
    assert a.key() == b.key()
    assert a.key() != FaultPlan.generate(8, 200, **kw).key()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000))
def test_plan_key_stable_across_regeneration(seed):
    kw = dict(steps=64, device_loss=0.05, link_degrade=0.1, delay=0.1,
              payload_corrupt=0.1)
    assert FaultPlan.generate(seed, **kw).key() == \
        FaultPlan.generate(seed, **kw).key()


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 500), st.integers(1, 64))
def test_step_events_fire_once_per_injector(seed, steps):
    plan = FaultPlan.generate(seed, steps, delay=0.3, link_degrade=0.2)
    inj = FaultInjector(plan)
    first = [ev for s in range(steps) for ev in inj.step_events(s)]
    again = [ev for s in range(steps) for ev in inj.step_events(s)]
    assert sorted(e.ident for e in first) == \
        sorted(e.ident for e in plan.events if e.kind in
               ("delay", "link_degrade", "link_restore"))
    assert again == []                    # replay after restore: no re-fire


def test_parse_spec_and_bare_seed():
    p = FaultPlan.parse("seed=7,steps=64,delay=0.5,payload_corrupt=0")
    assert p.seed == 7 and p.count("delay") > 0
    assert p.count("payload_corrupt") == 0
    assert p.events == FaultPlan.parse(" seed=7, steps=64, delay=0.5,"
                                       "payload_corrupt=0 ").events
    bare = FaultPlan.parse("41")
    assert bare.seed == 41
    assert bare.count("device_loss") == 0     # survivable defaults
    with pytest.raises(ValueError):
        FaultPlan.parse("seed=1,bogus=2")


def test_link_degrade_pairs_with_restore():
    plan = FaultPlan.generate(3, 200, link_degrade=0.2)
    degrades = [e for e in plan.events if e.kind == "link_degrade"]
    restores = {(e.target, e.at) for e in plan.events
                if e.kind == "link_restore"}
    assert degrades
    for d in degrades:
        assert 0.25 <= d.magnitude <= 0.75
        # bounded window: a matching restore exists unless it would land
        # past the end of the run
        assert any(t == d.target and d.at < at <= d.at + 8
                   for t, at in restores) or d.at + 8 >= 200


# ---------------------------------------------------------------------------
# FaultInjector: scoping, launch ordinals, file corruption
# ---------------------------------------------------------------------------
def test_injector_scoping_is_lifo(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert active_injector() is None
    outer, inner = FaultInjector(FaultPlan()), FaultInjector(FaultPlan())
    with outer:
        assert active_injector() is outer
        with inner:
            assert active_injector() is inner
        assert active_injector() is outer
    assert active_injector() is None


def test_env_var_arms_process_wide_injector(monkeypatch):
    monkeypatch.setenv(ENV_VAR, "seed=9,steps=16,delay=0.5")
    inj = active_injector()
    assert inj is not None
    assert inj.plan.key() == FaultPlan.parse("seed=9,steps=16,delay=0.5"
                                             ).key()
    # an explicitly-entered injector wins over the env one
    with FaultInjector(FaultPlan()) as scoped:
        assert active_injector() is scoped
    # a malformed spec never crashes the host process
    monkeypatch.setenv(ENV_VAR, "seed=9,not_a_fault=1")
    assert active_injector() is None


def test_check_launch_consumes_ordinals(quiet_faults):
    plan = FaultPlan(seed=1, events=(FaultEvent("payload_corrupt", 2),))
    with FaultInjector(plan) as inj:
        inj.check_launch("a")             # ordinal 0
        inj.check_launch("b")             # ordinal 1
        with pytest.raises(InjectedFault) as ei:
            inj.check_launch("c")         # ordinal 2: armed
        assert ei.value.event.kind == "payload_corrupt"
        inj.check_launch("d")             # fired once: ordinal 3 clean
        assert inj.stats()["launches"] == 4
        assert inj.stats()["fired"] == {"payload_corrupt": 1}


def test_corrupt_file_is_deterministic(tmp_path):
    payload = os.urandom(4096)
    p1, p2 = tmp_path / "blob.bin", tmp_path / "sub"
    p2.mkdir()
    p2 = p2 / "blob.bin"
    p1.write_bytes(payload)
    p2.write_bytes(payload)
    a = FaultInjector(FaultPlan(seed=5))
    b = FaultInjector(FaultPlan(seed=5))
    assert a.corrupt_file(str(p1)) and b.corrupt_file(str(p2))
    assert p1.read_bytes() == p2.read_bytes()     # seeded by (seed, name)
    assert p1.read_bytes() != payload[:len(p1.read_bytes())]
    assert p1.read_bytes().startswith(b"\x00CHAOS\x00")
    assert not a.corrupt_file(str(tmp_path / "missing.bin"))


# ---------------------------------------------------------------------------
# GuardedSchedule: retry -> fallback -> sticky demotion
# ---------------------------------------------------------------------------
def _stub_inner(fail_times: int = 0, value: int = 7):
    """Minimal CompiledSchedule stand-in for ladder-shape tests."""
    calls = {"n": 0}

    def run_numpy(X):
        calls["n"] += 1
        if calls["n"] <= fail_times:
            raise RuntimeError(f"boom {calls['n']}")
        return value

    stub = types.SimpleNamespace(plan_name="stub", n=4, num_blocks=4,
                                 run_numpy=run_numpy, calls=calls)
    return stub


def test_guard_retries_then_raises_without_fallback(quiet_faults):
    from repro.core.lower import GuardedSchedule, GuardPolicy
    gs = GuardedSchedule(_stub_inner(fail_times=99),
                         policy=GuardPolicy(max_retries=2, backoff=0.0))
    with pytest.raises(RuntimeError, match="boom"):
        gs.run_numpy(np.zeros((4, 4)))    # run_numpy has no flat rung
    assert gs.stats["launches"] == 1
    assert gs.stats["retries"] == 2
    assert gs.inner.calls["n"] == 3       # initial attempt + 2 retries
    assert not gs.demoted                 # no fallback taken -> no demotion


def test_guard_fallback_ladder_and_sticky_demotion(quiet_faults):
    from repro.core.lower import GuardedSchedule, GuardPolicy
    gs = GuardedSchedule(_stub_inner(),
                         policy=GuardPolicy(max_retries=1, backoff=0.0))
    attempts = {"n": 0}

    def attempt():
        attempts["n"] += 1
        raise RuntimeError("planned rung down")

    assert gs._guarded("allreduce", attempt, lambda: "flat") == "flat"
    assert attempts["n"] == 2             # retry bounded, then fallback
    assert gs.stats["fallbacks"] == 1 and gs.demoted
    # demotion is sticky: the next launch takes the flat rung directly
    assert gs._guarded("allreduce", attempt, lambda: "flat") == "flat"
    assert attempts["n"] == 2
    assert gs.stats["demoted_launches"] == 1
    gs.reset_guard()
    assert gs._guarded("allreduce", lambda: "planned",
                       lambda: "flat") == "planned"


def test_guard_timeout_is_posthoc_demotion(quiet_faults):
    from repro.core.lower import GuardedSchedule, GuardPolicy
    gs = GuardedSchedule(_stub_inner(),
                         policy=GuardPolicy(timeout=0.0, backoff=0.0))
    # the overrunning launch still returns its (valid) result...
    assert gs._guarded("allreduce", lambda: 42, lambda: "flat") == 42
    assert gs.stats["timeouts"] == 1 and gs.demoted
    # ...and subsequent launches are served by the flat rung
    assert gs._guarded("allreduce", lambda: 42, lambda: "flat") == "flat"


def test_injected_payload_fault_exercises_retry(monkeypatch):
    from repro.core.lower import GuardedSchedule, GuardPolicy
    monkeypatch.delenv(ENV_VAR, raising=False)
    plan = FaultPlan(seed=1, events=(FaultEvent("payload_corrupt", 0),))
    gs = GuardedSchedule(_stub_inner(),
                         policy=GuardPolicy(max_retries=1, backoff=0.0))
    with FaultInjector(plan):
        # launch ordinal 0 is armed: check_launch raises before the
        # planned attempt runs, the retry (ordinal 1) goes through
        assert gs._guarded("allreduce", lambda: "planned",
                           lambda: "flat") == "planned"
    assert gs.stats["retries"] == 1
    assert gs.stats["fallbacks"] == 0 and not gs.demoted


def test_guarded_run_numpy_matches_inner(quiet_faults):
    from repro.core.lower import GuardedSchedule, guard_schedule
    from repro.planner.service import PlannerService
    ex = PlannerService().get_axis_executable("data", 4, 4096.0)
    gs = guard_schedule(ex.schedule)
    assert isinstance(gs, GuardedSchedule)
    X = np.random.default_rng(0).normal(size=(4, 32))
    np.testing.assert_allclose(gs.run_numpy(X), ex.schedule.run_numpy(X))
    # wrapper is a drop-in: delegated attrs reach the inner schedule
    assert gs.n == ex.schedule.n
    assert gs.describe() == ex.schedule.describe()


def test_guard_schedule_is_memoized(quiet_faults):
    from repro.core.lower import guard_schedule
    from repro.planner.service import PlannerService
    sched = PlannerService().get_axis_executable("data", 4, 4096.0).schedule
    g1 = guard_schedule(sched)
    g2 = guard_schedule(sched)
    assert g1 is g2                       # sticky demotion survives re-wrap
    assert guard_schedule(g1) is g1       # idempotent
    assert guard_schedule(None) is None


def test_link_restore_reprobes_demoted_guard(quiet_faults):
    """ISSUE 9 satellite: sticky wire/plan demotion must clear when link
    health is restored — a transient fault may not pin the mesh to flat
    psum forever. `PlannerService.mark_degraded(level, 1.0)` (the
    runtime.ft link_restore path) re-probes every live demoted guard."""
    from repro.core.lower import GuardedSchedule, GuardPolicy
    from repro.planner.service import PlannerService
    gs = GuardedSchedule(_stub_inner(),
                         policy=GuardPolicy(max_retries=0, backoff=0.0))

    def boom():
        raise RuntimeError("link down")

    assert gs._guarded("allreduce", boom, lambda: "flat") == "flat"
    assert gs.demoted
    svc = PlannerService()
    svc.mark_degraded("root_sw", 0.5)     # degradation: demotion stays
    assert gs.demoted
    svc.mark_degraded("root_sw", 1.0)     # restoration: re-probe
    assert not gs.demoted
    assert gs.stats["reprobes"] == 1
    # the next launch tries the planned rung again
    assert gs._guarded("allreduce", lambda: "planned",
                       lambda: "flat") == "planned"


def test_fault_plan_link_restore_reprobes_through_ft(quiet_faults,
                                                     tmp_path):
    """End-to-end: a link_degrade → link_restore fault-plan event stream
    replayed through FaultTolerantLoop._apply_fault re-probes the guard
    (ft calls mark_degraded(target, 1.0) on restore)."""
    from repro.core.lower import GuardedSchedule, GuardPolicy
    from repro.planner.service import PlannerService
    from repro.checkpoint import CheckpointManager
    from repro.runtime.ft import FaultTolerantLoop

    gs = GuardedSchedule(_stub_inner(),
                         policy=GuardPolicy(max_retries=0, backoff=0.0))
    gs._guarded("allreduce", _raise_link_down, lambda: "flat")
    assert gs.demoted
    svc = PlannerService()
    loop = FaultTolerantLoop(lambda s, i: s, {"w": 0},
                             CheckpointManager(str(tmp_path)), planner=svc)
    events = []
    loop.on_event = lambda kind, info: events.append(kind)
    loop._apply_fault(FaultEvent("link_degrade", 0, magnitude=0.5,
                                 target="root_sw"), step=0)
    assert gs.demoted                      # degraded: replan, stay flat
    loop._apply_fault(FaultEvent("link_restore", 1, magnitude=1.0,
                                 target="root_sw"), step=1)
    assert not gs.demoted                  # restored: planned rung re-armed
    assert events == ["degrade", "restore"]


def _raise_link_down():
    raise RuntimeError("link down")


# ---------------------------------------------------------------------------
# PlanCache: corrupted persistence never crashes startup
# ---------------------------------------------------------------------------
def test_cache_load_corrupt_file_is_cold_start(tmp_path):
    from repro.planner.cache import PlanCache
    path = tmp_path / "plans.json"
    path.write_text("{ not json !!")
    cache = PlanCache(path=str(path))     # auto-loads at construction
    assert cache.stats.load_errors == 1
    assert len(cache) == 0
    assert cache.load() == 0              # explicit retry: still no crash


def test_cache_load_skips_bad_entries(tmp_path):
    from repro.planner.cache import PlanCache
    path = tmp_path / "plans.json"
    path.write_text(json.dumps({"version": 1, "entries": {
        "good": {"axis_plans": [["data", 4, "cps"]]},
        "torn_plan": {"plan": {"truncated": True}, "algo": "cps",
                      "predicted_time": 1e-3},
        "not_a_dict": 5,
    }, "stats": {}}))
    cache = PlanCache(path=str(path))     # auto-loads at construction
    assert cache.stats.load_errors == 2   # only the intact entry survives
    assert len(cache) == 1
    assert cache.stats.disk_loads == 1


def test_cache_survives_injector_corruption(tmp_path, quiet_faults):
    from repro.planner.cache import PlanCache
    from repro.planner.service import PlannerService
    path = str(tmp_path / "plans.json")
    svc = PlannerService(cache=PlanCache(path=path))
    svc.get_axis_executable("data", 4, 4096.0)
    svc.cache.save()
    assert len(PlanCache(path=path)) >= 1        # intact round-trip
    assert FaultInjector(FaultPlan(seed=3)).corrupt_file(path)
    cold = PlanCache(path=path)
    assert len(cold) == 0                 # corrupt file -> cold, no raise
    assert cold.stats.load_errors == 1


# ---------------------------------------------------------------------------
# CheckpointManager: checksum manifest + fallback restore
# ---------------------------------------------------------------------------
def _ckpt_tree(v: float):
    return {"w": np.full((4,), v, np.float32), "step": np.int64(v)}


def test_checkpoint_checksums_written_and_verified(tmp_path):
    from repro.checkpoint.store import (CHECKSUM_FILE, CheckpointManager,
                                        verify_checksums)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, _ckpt_tree(1.0))
    path = tmp_path / "step_00000001"
    assert (path / CHECKSUM_FILE).exists()
    assert verify_checksums(str(path)) and mgr.verify(1)
    (path / "arrays.npz").write_bytes(b"\x00flip")
    assert not verify_checksums(str(path)) and not mgr.verify(1)


def test_restore_falls_back_past_corrupt_checkpoint(tmp_path, quiet_faults):
    from repro.checkpoint.store import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(10, _ckpt_tree(10.0))
    mgr.save(20, _ckpt_tree(20.0))
    inj = FaultInjector(FaultPlan(seed=11))
    assert inj.corrupt_file(str(tmp_path / "step_00000020" / "arrays.npz"))
    tree, step = mgr.restore(_ckpt_tree(0.0))
    assert step == 10                     # newest intact wins
    np.testing.assert_array_equal(np.asarray(tree["w"]),
                                  np.full((4,), 10.0, np.float32))
    # an explicit step is authoritative: corruption there raises
    with pytest.raises(Exception):
        mgr.restore(_ckpt_tree(0.0), step=20)


def test_restore_raises_when_everything_is_corrupt(tmp_path, quiet_faults):
    from repro.checkpoint.store import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(5, _ckpt_tree(5.0))
    inj = FaultInjector(FaultPlan(seed=2))
    assert inj.corrupt_file(str(tmp_path / "step_00000005" / "arrays.npz"))
    with pytest.raises(FileNotFoundError, match="no intact checkpoint"):
        mgr.restore(_ckpt_tree(0.0))


# ---------------------------------------------------------------------------
# Fault-tolerant loop: injected faults, bounded events, budget decay
# ---------------------------------------------------------------------------
def test_watchdog_event_log_is_bounded():
    from repro.runtime.ft import StragglerWatchdog
    wd = StragglerWatchdog(threshold=2.0, max_events=4)
    wd.observe(0, 0.01)                   # seeds the EWMA baseline
    for step in range(1, 40):
        wd.observe(step, 5.0)             # every step straggles
    assert len(wd.events) == 4
    assert wd.events[-1][0] == 39         # deque keeps the freshest


def test_loop_replays_injected_device_loss_and_forgives(tmp_path,
                                                        monkeypatch):
    from repro.checkpoint import CheckpointManager
    from repro.runtime.ft import FaultTolerantLoop
    monkeypatch.delenv(ENV_VAR, raising=False)
    plan = FaultPlan(seed=1, events=(FaultEvent("device_loss", 3),
                                     FaultEvent("delay", 1,
                                                magnitude=0.001)))
    events = []
    loop = FaultTolerantLoop(
        lambda state, step: {"x": state["x"] + 1.0},
        {"x": np.float64(0.0)},
        CheckpointManager(str(tmp_path), async_save=False),
        ckpt_every=2, injector=FaultInjector(plan), forgive_after=2,
        on_event=lambda kind, info: events.append(kind))
    out = loop.run(8)
    kinds = set(events)
    assert float(out["x"]) == 8.0         # restore-and-replay is exact
    assert "failure" in kinds
    # 2 successful post-failure steps reset the restart budget
    assert "budget_reset" in kinds
    assert loop.restarts == 0


def test_loop_link_fault_flows_into_planner_health(tmp_path, monkeypatch):
    from repro.checkpoint import CheckpointManager
    from repro.planner.service import PlannerService
    from repro.runtime.ft import FaultTolerantLoop
    monkeypatch.delenv(ENV_VAR, raising=False)
    svc = PlannerService()
    plan = FaultPlan(seed=1, events=(
        FaultEvent("link_degrade", 1, "root_sw", 0.5),
        FaultEvent("link_restore", 3, "root_sw")))
    seen = []
    mid_run_health = {}

    def step_fn(state, step):
        if step == 2:
            mid_run_health.update(svc.degraded())
        return {"x": state["x"] + 1.0}

    loop = FaultTolerantLoop(
        step_fn, {"x": np.float64(0.0)},
        CheckpointManager(str(tmp_path), async_save=False),
        ckpt_every=10, planner=svc, injector=FaultInjector(plan),
        on_event=lambda kind, info: seen.append((kind, dict(info))))
    loop.run(5)
    assert mid_run_health == {"root_sw": 0.5}     # degraded mid-run...
    assert svc.degraded() == {}                   # ...restored by the end
    kinds = [k for k, _ in seen]
    assert "degrade" in kinds and "restore" in kinds


# ---------------------------------------------------------------------------
# Refit guardrails: validate / clamp / quarantine
# ---------------------------------------------------------------------------
def test_validate_params_rejects_garbage():
    from repro.core.cost_model import GenModelParams, TPU_V5E
    from repro.planner.calibrate import validate_params
    ok = TPU_V5E["root_sw"]
    assert validate_params(ok) == []
    import dataclasses
    assert validate_params(dataclasses.replace(ok, alpha=float("nan")))
    assert validate_params(dataclasses.replace(ok, beta=-1e-12))
    assert validate_params(dataclasses.replace(ok, gamma=1.0))  # implausible
    assert validate_params(GenModelParams(w_t=0))
    assert validate_params(dataclasses.replace(ok, delta=float("inf")))


def test_clamp_params_bounds_per_refit_movement():
    import dataclasses
    from repro.core.cost_model import TPU_V5E
    from repro.planner.calibrate import DEFAULT_GUARD, clamp_params
    old = TPU_V5E["root_sw"]
    wild = dataclasses.replace(old, alpha=old.alpha * 100.0,
                               beta=old.beta / 100.0)
    new, clamped = clamp_params(old, wild)
    r = DEFAULT_GUARD.max_step_ratio
    assert new.alpha == pytest.approx(old.alpha * r)
    assert new.beta == pytest.approx(old.beta / r)
    assert set(clamped) == {"alpha", "beta"}
    same, untouched = clamp_params(old, old)
    assert untouched == [] and same == old


def test_quarantine_outliers_drops_fault_window_samples():
    from repro.planner.calibrate import quarantine_outliers

    def s(n, size, cps):
        return types.SimpleNamespace(n=n, size_floats=size,
                                     cps_equivalent=cps)

    group = [s(8, 1e6, 1.0), s(8, 1e6, 1.1), s(8, 1e6, 0.9),
             s(8, 1e6, 50.0)]            # retry-storm outlier
    tiny = [s(4, 1e5, 99.0)]             # group < 3: kept whole
    kept, quarantined = quarantine_outliers(group + tiny, k=4.0)
    assert [q.cps_equivalent for q in quarantined] == [50.0]
    assert len(kept) == 4


def test_refit_rejects_nan_fit_and_keeps_params(monkeypatch):
    import repro.planner.service as service_mod
    from repro.core.cost_model import GenModelParams
    from repro.planner.calibrate import CalibrationResult
    from repro.planner.service import PlannerService

    svc = PlannerService()
    poisoned = GenModelParams(alpha=float("nan"), beta=-1e-9)
    monkeypatch.setattr(
        service_mod, "calibrate_levels",
        lambda source, cfg, provider=None: CalibrationResult(
            params={"root_sw": poisoned}))
    res = svc._refit_level("root_sw", drift=1.0, observations=8)
    assert res["rejected"]                # violations reported
    assert svc.params is None             # pricing basis untouched
    ev = svc.refits[-1]
    assert ev["level"] == "root_sw" and ev["rejected"]
    assert svc.stats()["refits"][-1]["rejected"]


def test_refit_clamps_implausible_jump(monkeypatch):
    import dataclasses
    import repro.planner.service as service_mod
    from repro.core.cost_model import TPU_V5E
    from repro.planner.calibrate import CalibrationResult
    from repro.planner.service import PlannerService

    svc = PlannerService(params=TPU_V5E)
    old = svc._merged_level_params("root_sw", svc.params)
    jump = dataclasses.replace(old, beta=old.beta * 1000.0)
    monkeypatch.setattr(
        service_mod, "calibrate_levels",
        lambda source, cfg, provider=None: CalibrationResult(
            params={"root_sw": jump}))
    res = svc._refit_level("root_sw", drift=1.0, observations=8)
    assert "rejected" not in res
    assert svc.refits[-1]["clamped"] == ["beta"]
    got = svc.params["root_sw"].beta
    assert got == pytest.approx(old.beta * 8.0)   # max_step_ratio bound
    assert got < jump.beta


# ---------------------------------------------------------------------------
# Degraded-mode replanning: health -> fingerprint -> fresh plan
# ---------------------------------------------------------------------------
def test_topology_health_changes_canonical_form():
    from repro.core.topology import single_switch
    from repro.planner.fingerprint import fingerprint_topo, topo_canonical
    t = single_switch(4)
    base = fingerprint_topo(t)
    t.children[0].mark_degraded(0.5)
    assert topo_canonical(t) != topo_canonical(single_switch(4))
    assert fingerprint_topo(t) != base
    t.children[0].restore_health()
    assert fingerprint_topo(t) == base    # restore is exact
    assert t.children[0].uplink_bw == single_switch(4).children[0].uplink_bw


def test_prune_dead_drops_subtree():
    from repro.core.topology import single_switch
    t = single_switch(4)
    t.children[1].mark_dead()
    assert t.has_dead()
    pruned = t.prune_dead()
    assert not pruned.has_dead()
    assert len(pruned.server_ids()) == 3
    for c in t.children:
        c.mark_dead()
    with pytest.raises(ValueError):
        t.prune_dead()


def test_mark_degraded_replans_under_new_fingerprint(quiet_faults):
    from repro.planner.service import PlannerService
    svc = PlannerService()
    healthy = svc.get_axis_executable("data", 8, 65536.0)
    dropped = svc.mark_degraded("root_sw", 0.5)
    assert dropped >= 0
    assert svc.degraded() == {"root_sw": 0.5}
    assert svc.stats()["degraded"] == {"root_sw": 0.5}
    degraded = svc.get_axis_executable("data", 8, 65536.0)
    assert degraded.key != healthy.key    # replanned, not re-served
    # pricing reflects the sag: same plan shape costs more on half bw
    assert degraded.predicted_time > healthy.predicted_time
    svc.clear_degraded()
    assert svc.degraded() == {}
    assert svc.get_axis_executable("data", 8, 65536.0).key == healthy.key


def test_degrade_never_bakes_into_stored_params():
    from repro.core.cost_model import TPU_V5E
    from repro.planner.service import PlannerService
    svc = PlannerService(params=TPU_V5E)
    svc.mark_degraded("root_sw", 0.25)
    eff = svc._effective_axis_params()
    assert eff["root_sw"].beta == pytest.approx(
        TPU_V5E["root_sw"].beta / 0.25)
    # the stored basis is still nominal: a later restore is lossless
    assert svc.params["root_sw"].beta == TPU_V5E["root_sw"].beta
    svc.clear_degraded()
    assert svc._effective_axis_params()["root_sw"].beta == \
        TPU_V5E["root_sw"].beta


# ---------------------------------------------------------------------------
# 8-device chaos soak: faulted run == fault-free run
# ---------------------------------------------------------------------------
_SOAK_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.pop("REPRO_FAULT_PLAN", None)
import json
import tempfile
import jax
import numpy as np
from repro.launch.train import TrainConfig, run_training
from repro.launch.mesh import make_mesh
from repro.planner.service import default_service
from repro.runtime.faults import FaultEvent, FaultInjector, FaultPlan
from repro.runtime.metrics import default_metrics

results = {}
mesh = make_mesh((8, 1), ("data", "model"))
kw = dict(arch="rwkv6-1.6b", steps=24, seq_len=32, global_batch=8,
          lr=1e-3, log_every=1000, engine="manual", sync="plan",
          ckpt_every=6, observe_sync=False)

clean = run_training(TrainConfig(**kw, ckpt_dir=tempfile.mkdtemp()),
                     mesh=mesh)

# deterministic chaos: a device loss mid-run, a root-switch bandwidth sag
# with bounded restore, a corrupted newest checkpoint, and a second
# device loss that forces the restore to fall back past the corruption
plan = FaultPlan(seed=7, events=(
    FaultEvent("delay", 5, magnitude=0.02),
    FaultEvent("device_loss", 8),
    FaultEvent("link_degrade", 14, "root_sw", 0.5),
    FaultEvent("link_restore", 17, "root_sw"),
    FaultEvent("file_corrupt", 20, "checkpoint"),
    FaultEvent("device_loss", 21),
))
injector = FaultInjector(plan)
with injector:
    chaos = run_training(TrainConfig(**kw, ckpt_dir=tempfile.mkdtemp()),
                         mesh=mesh)

fired = injector.stats()["fired"]
results["fired"] = fired
results["loss_clean"] = clean["losses"][-1]
results["loss_chaos"] = chaos["losses"][-1]
cl = jax.tree.leaves(clean["state"]["params"])
ch = jax.tree.leaves(chaos["state"]["params"])
results["param_max_rel"] = max(
    float(np.max(np.abs(np.asarray(a, np.float64) -
                        np.asarray(b, np.float64))) /
          (np.max(np.abs(np.asarray(a, np.float64))) + 1e-30))
    for a, b in zip(cl, ch))

svc = default_service()
results["degraded_after"] = svc.degraded()
snap = default_metrics().snapshot()


def ctr(name):
    return snap.get(name, {}).get("value", 0)


results["degrade_events"] = ctr("planner_degrade_events_total")
results["ckpt_fallbacks"] = ctr("ckpt_restore_fallbacks_total")
results["restarts"] = ctr("ft_restarts_total")
results["files_corrupted"] = ctr("faults_files_corrupted_total")
results["guarded_launches"] = ctr("guarded_launches_total")

# the live service replans degraded levels under a fresh fingerprint
e1 = svc.get_axis_executable("data", 8, 65536.0)
svc.mark_degraded("root_sw", 0.5)
e2 = svc.get_axis_executable("data", 8, 65536.0)
svc.clear_degraded()
results["fingerprint_changed"] = bool(e1.key != e2.key)

# no refit ever committed NaN/negative params
from repro.planner.calibrate import validate_params
params = svc.params or {}
results["params_valid"] = all(not validate_params(p)
                              for p in params.values())
results["refits_rejected_kept_basis"] = all(
    not r.get("rejected") or "params" not in r
    for r in svc.stats()["refits"])
print("RESULTS " + json.dumps(results))
"""


@pytest.fixture(scope="module")
def soak():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop(ENV_VAR, None)
    out = subprocess.run([sys.executable, "-c", _SOAK_DRIVER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


def test_soak_fires_required_faults(soak):
    fired = soak["fired"]
    assert fired.get("device_loss", 0) >= 1
    assert fired.get("link_degrade", 0) >= 1
    assert soak["files_corrupted"] >= 1
    assert soak["restarts"] >= 2          # both device losses restarted
    assert soak["ckpt_fallbacks"] >= 1    # corrupt ckpt skipped on restore


def test_soak_matches_fault_free_run(soak):
    assert abs(soak["loss_chaos"] - soak["loss_clean"]) <= \
        1e-6 * max(1.0, abs(soak["loss_clean"])), soak
    assert soak["param_max_rel"] <= 1e-6, soak


def test_soak_planner_replans_and_heals(soak):
    assert soak["degrade_events"] >= 2    # degrade + restore transitions
    assert soak["degraded_after"] == {}   # health restored by run end
    assert soak["fingerprint_changed"]
    assert soak["guarded_launches"] >= 1


def test_soak_refits_never_commit_garbage(soak):
    assert soak["params_valid"]
    assert soak["refits_rejected_kept_basis"]
