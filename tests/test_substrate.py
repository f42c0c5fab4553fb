"""Data pipeline, optimizer, checkpointing, fault tolerance."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_pytree, save_pytree
from repro.data import DataConfig, SyntheticLM, make_pipeline
from repro.optim import AdamWConfig, adamw_init, adamw_update, warmup_cosine
from repro.runtime import FaultTolerantLoop, StragglerWatchdog


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------
def test_data_deterministic_in_step():
    cfg = DataConfig(vocab=100, seq_len=16, global_batch=4, seed=3)
    a = SyntheticLM(cfg).batch_at(7)
    b = SyntheticLM(cfg).batch_at(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = SyntheticLM(cfg).batch_at(8)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_data_labels_shifted():
    cfg = DataConfig(vocab=100, seq_len=16, global_batch=2)
    b = SyntheticLM(cfg).batch_at(0)
    assert b["tokens"].shape == (2, 16) and b["labels"].shape == (2, 16)
    # label t is token t+1 of the underlying stream:
    assert (b["labels"][:, :-1] == b["tokens"][:, 1:]).all()


def test_pipeline_prefetch_resume():
    cfg = DataConfig(vocab=50, seq_len=8, global_batch=2)
    it = make_pipeline(cfg, start_step=5, prefetch=2)
    first = next(it)
    np.testing.assert_array_equal(first["tokens"],
                                  SyntheticLM(cfg).batch_at(5)["tokens"])


def test_data_learnable_structure():
    cfg = DataConfig(vocab=100, seq_len=64, global_batch=8)
    b = SyntheticLM(cfg).batch_at(0)
    follow = (b["tokens"] * 7 + 3) % 100
    frac = (b["labels"] == follow).mean()
    assert frac > 0.4          # markov_mix=0.65 minus collisions


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_reduces_quadratic():
    params = {"w": jnp.array([3.0, -2.0])}
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0)

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    for _ in range(200):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw_update(params, g, opt, cfg)
    assert float(loss(params)) < 1e-3


def test_adamw_grad_clip():
    params = {"w": jnp.ones((4,))}
    opt = adamw_init(params)
    g = {"w": jnp.full((4,), 100.0)}
    _, _, gnorm = adamw_update(params, g, opt,
                               AdamWConfig(lr=0.0, grad_clip=1.0))
    assert float(gnorm) == pytest.approx(200.0)


def test_warmup_cosine_shape():
    lr0 = float(warmup_cosine(0, peak=1.0, warmup=10, total=100))
    lr10 = float(warmup_cosine(10, peak=1.0, warmup=10, total=100))
    lr100 = float(warmup_cosine(100, peak=1.0, warmup=10, total=100))
    assert lr0 == 0.0 and lr10 == pytest.approx(1.0)
    assert lr100 == pytest.approx(0.1, abs=1e-6)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------
def test_save_load_roundtrip(tmp_path):
    tree = {"a": jnp.arange(5, dtype=jnp.bfloat16),
            "b": {"c": jnp.ones((2, 3))}, "step": jnp.int32(7)}
    save_pytree(tree, str(tmp_path / "ck"))
    out = load_pytree(str(tmp_path / "ck"), tree)
    assert out["step"] == 7
    assert out["a"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out["b"]["c"]),
                                  np.ones((2, 3)))


def test_manager_latest_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    tree = {"x": jnp.zeros(3)}
    for s in (10, 20, 30):
        mgr.save(s, {"x": jnp.full(3, float(s))})
    assert mgr.latest_step() == 30
    restored, step = mgr.restore(tree)
    assert step == 30 and float(restored["x"][0]) == 30.0
    dirs = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert len(dirs) == 2       # gc keeps 2


def test_manager_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=True)
    mgr.save(1, {"x": jnp.ones(4)})
    mgr.wait()
    assert mgr.latest_step() == 1


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------
def test_ft_loop_recovers_from_failures(tmp_path):
    """Inject a failure at step 7; the loop must restore step-5 state and
    produce the exact same final state as a failure-free run."""
    def make_loop(fail_once, path):
        mgr = CheckpointManager(path, keep=3, async_save=False)
        seen = {"failed": False}

        def step_fn(state, step):
            if fail_once and step == 7 and not seen["failed"]:
                seen["failed"] = True
                raise RuntimeError("injected device loss")
            return {"acc": state["acc"] + step}

        return FaultTolerantLoop(step_fn, {"acc": jnp.float32(0)}, mgr,
                                 ckpt_every=5)

    clean = make_loop(False, str(tmp_path / "a")).run(12)
    faulty_loop = make_loop(True, str(tmp_path / "b"))
    faulty = faulty_loop.run(12)
    assert float(clean["acc"]) == float(faulty["acc"])
    assert faulty_loop.restarts == 1


def test_ft_loop_resumes_from_disk(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(10, {"acc": jnp.float32(45.0)})   # sum of 0..9

    def step_fn(state, step):
        return {"acc": state["acc"] + step}

    loop = FaultTolerantLoop(step_fn, {"acc": jnp.float32(0)}, mgr,
                             ckpt_every=100)
    out = loop.run(12)
    assert float(out["acc"]) == sum(range(12))


def test_straggler_watchdog():
    wd = StragglerWatchdog(threshold=2.0, halflife=5)
    for s in range(20):
        assert not wd.observe(s, 1.0)
    assert wd.observe(20, 5.0)          # 5× the EWMA
    assert wd.events and wd.events[0][0] == 20
    # baseline not poisoned by the straggler
    assert not wd.observe(21, 1.2)


def test_elastic_remesh_identity():
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.runtime import elastic_remesh
    mesh = make_mesh((1,), ("data",))
    state = {"w": jnp.ones((4, 4))}
    sh = {"w": NamedSharding(mesh, P())}
    out = elastic_remesh(state, sh)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones((4, 4)))


# ---------------------------------------------------------------------------
# replanning: bucket schedules must not survive an axis-size change
# ---------------------------------------------------------------------------
def test_elastic_remesh_invalidates_bucket_schedules():
    """An elastic remesh changes axis sizes; every lowered
    CompiledSchedule and bucket plan derived from the planner cache must
    be dropped, and the next lookup must rebuild against the new size."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.planner.service import PlannerService
    from repro.runtime import elastic_remesh

    svc = PlannerService()
    bp8 = svc.get_bucket_plan([("data", 8)], 4096.0)
    assert bp8.axis_plans[0].schedule.n == 8
    assert svc.executable_count() > 0

    mesh = make_mesh((1,), ("data",))
    state = {"w": jnp.ones((4, 4))}
    out = elastic_remesh(state, {"w": NamedSharding(mesh, P())},
                         planner=svc)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones((4, 4)))
    assert svc.executable_count() == 0          # stale schedules gone

    bp4 = svc.get_bucket_plan([("data", 4)], 4096.0)
    assert bp4.source == "cold"
    assert bp4.axis_plans[0].schedule.n == 4
    assert bp4.axis_plans[0].schedule is not bp8.axis_plans[0].schedule


def test_precision_change_never_serves_stale_compressed_plan():
    """The PR 4 fingerprint-invalidation contract extended to the wire
    precision (DESIGN.md §13): precision and tolerance live in
    BucketConfig.key(), so changing either is a cold cache miss — a
    caller that revokes lossy consent can never be handed a cached
    compressed schedule, and vice versa."""
    from repro.core.bucketing import BucketConfig
    from repro.planner.service import PlannerService

    svc = PlannerService()
    lossy = svc.get_bucket_plan(
        [("data", 8)], 65536.0,
        config=BucketConfig(precision="fp8", tolerance=0.3))
    assert lossy.source == "cold" and lossy.precision == "fp8"
    assert lossy.axis_plans[0].schedule.wire.name == "fp8"

    # tolerance revoked: cold miss, full-precision plan, no wire
    strict = svc.get_bucket_plan(
        [("data", 8)], 65536.0,
        config=BucketConfig(precision="fp8", tolerance=None))
    assert strict.source == "cold" and strict.precision == "fp8"
    # precision=fp8 with tolerance=None is an explicit pin (trusted) —
    # but a *float* tolerance below the budget clamps
    clamped = svc.get_bucket_plan(
        [("data", 8)], 65536.0,
        config=BucketConfig(precision="fp8", tolerance=0.01))
    assert clamped.source == "cold" and clamped.precision == "f32"
    assert clamped.axis_plans[0].schedule.wire is None

    # default (no consent at all) is lossless and its own entry
    plain = svc.get_bucket_plan([("data", 8)], 65536.0)
    assert plain.source == "cold" and plain.precision == "f32"
    assert plain.axis_plans[0].schedule is not \
        lossy.axis_plans[0].schedule

    # warm hits for each key keep their own choice
    assert svc.get_bucket_plan(
        [("data", 8)], 65536.0,
        config=BucketConfig(precision="fp8",
                            tolerance=0.3)).precision == "fp8"
    assert svc.get_bucket_plan([("data", 8)], 65536.0).precision == "f32"

    # schedule invalidation rebuilds the wire binding, not just f32
    svc.invalidate_executables()
    re = svc.get_bucket_plan(
        [("data", 8)], 65536.0,
        config=BucketConfig(precision="fp8", tolerance=0.3))
    assert re.axis_plans[0].schedule.wire.name == "fp8"


def test_ft_resume_invalidates_and_rebuilds_bucket_schedules(tmp_path):
    """FaultTolerantLoop resume (restore from disk — possibly onto a
    different allocation) drops the derived schedules and reports it via
    the event hook; fresh lookups re-lower for the new mesh."""
    from repro.planner.service import PlannerService

    svc = PlannerService()
    svc.get_bucket_plan([("data", 8)], 8192.0)
    assert svc.executable_count() > 0

    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(10, {"acc": jnp.float32(45.0)})   # sum of 0..9
    events = []
    loop = FaultTolerantLoop(
        lambda s, i: {"acc": s["acc"] + i}, {"acc": jnp.float32(0)}, mgr,
        ckpt_every=100, planner=svc,
        on_event=lambda kind, info: events.append((kind, info)))
    out = loop.run(12)
    assert float(out["acc"]) == sum(range(12))

    kinds = [k for k, _ in events]
    assert "resume" in kinds and "invalidate" in kinds
    inv = dict(events)["invalidate"]
    assert inv["dropped"] > 0
    assert svc.executable_count() == 0
    # replanning after the (conceptual) axis-size change
    bp = svc.get_bucket_plan([("data", 4)], 8192.0)
    assert bp.axis_plans[0].schedule.n == 4


def test_ft_failure_restart_invalidates_bucket_schedules(tmp_path):
    """The failure-restart path restores a checkpoint too — it must drop
    the derived schedules exactly like a cold resume."""
    from repro.planner.service import PlannerService

    svc = PlannerService()
    svc.get_bucket_plan([("data", 8)], 4096.0)
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    seen = {"failed": False}

    def step_fn(state, step):
        if step == 7 and not seen["failed"]:
            seen["failed"] = True
            raise RuntimeError("injected device loss")
        return {"acc": state["acc"] + step}

    loop = FaultTolerantLoop(step_fn, {"acc": jnp.float32(0)}, mgr,
                             ckpt_every=5, planner=svc)
    out = loop.run(12)
    assert float(out["acc"]) == sum(range(12))
    assert loop.restarts == 1
    assert svc.executable_count() == 0


def test_ft_restart_without_checkpoint_invalidates(tmp_path):
    """A failure before the first checkpoint restarts from step 0 with no
    restore — the stale schedules must still be dropped (the failure may
    mean a new allocation)."""
    from repro.planner.service import PlannerService

    svc = PlannerService()
    svc.get_bucket_plan([("data", 8)], 4096.0)
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    seen = {"failed": False}

    def step_fn(state, step):
        if step == 3 and not seen["failed"]:
            seen["failed"] = True
            raise RuntimeError("injected device loss")
        return {"acc": state["acc"] + step}

    loop = FaultTolerantLoop(step_fn, {"acc": jnp.float32(0)}, mgr,
                             ckpt_every=50, planner=svc)
    out = loop.run(6)
    # no checkpoint: in-memory state survives the restart (steps 0-2
    # already applied) and the loop replays 0..5 on top
    assert float(out["acc"]) == sum(range(3)) + sum(range(6))
    assert loop.restarts == 1
    assert svc.executable_count() == 0


def test_ft_resume_invalidation_opt_out(tmp_path):
    from repro.planner.service import PlannerService

    svc = PlannerService()
    svc.get_bucket_plan([("data", 8)], 4096.0)
    before = svc.executable_count()
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(10, {"acc": jnp.float32(45.0)})
    loop = FaultTolerantLoop(
        lambda s, i: {"acc": s["acc"] + i}, {"acc": jnp.float32(0)}, mgr,
        ckpt_every=100, planner=svc, invalidate_on_resume=False)
    loop.run(12)
    assert svc.executable_count() == before     # schedules kept


# ---------------------------------------------------------------------------
# the online loop: observe -> drift -> refit -> invalidate -> replan
# ---------------------------------------------------------------------------
def _drifted_cluster(true_params, svc, level="root_sw"):
    """Ground-truth measurement oracle: what the cluster ACTUALLY takes
    is the service's chosen plan simulated under the true params."""
    from repro.core.simulator import Simulator
    from repro.core.sync import level_switch_topo

    def measure(n, size):
        resp = svc.get_axis_executable("data", n, size, level=level)
        topo = level_switch_topo(n, true_params, level)
        meas = Simulator(topo, true_params,
                         unit_bytes=4).simulate(resp.plan).total
        return resp, meas

    return measure


def test_refit_fires_and_invalidates_stale_plans():
    """Satellite: mis-seed GenModelParams, feed synthetic measurements
    until the refit fires; (a) old fingerprints miss, (b) derived_count
    drops to zero, (c) the next sync step lowers fresh schedules."""
    import dataclasses

    from repro.core.cost_model import PAPER_TABLE5
    from repro.planner.service import PlannerService, RefitPolicy

    true = PAPER_TABLE5
    wrong = dict(true)
    wrong["root_sw"] = dataclasses.replace(
        true["root_sw"], alpha=true["root_sw"].alpha / 3,
        beta=true["root_sw"].beta / 6)
    svc = PlannerService(params=wrong, refit_policy=RefitPolicy(
        min_samples=6, drift_threshold=0.15, cooldown=6))
    measure = _drifted_cluster(true, svc)

    bp_old = svc.get_bucket_plan([("data", 8)], float(1 << 18))
    sched_old = bp_old.axis_plans[0].schedule
    assert svc.cache.derived_count() > 0
    misses_before_refit = None

    sizes = [(8, 1e6), (8, 4e6), (4, 1e6), (8, 1.6e7), (4, 4e6),
             (8, 2e6), (8, 8e6), (4, 2e6)]
    fired = False
    for n, size in sizes * 3:
        resp, meas = measure(n, size)
        out = svc.observe("root_sw", n, size, meas,
                          predicted=resp.predicted_time, key=resp.key)
        if out["refit"]:
            fired = True
            assert out["dropped"] > 0
            misses_before_refit = svc.cache.stats.misses
            break
    assert fired, "drift never triggered a refit"
    # (b) every derived executable artifact dropped at the swap
    assert svc.cache.derived_count() == 0
    assert svc.refits and svc.refits[0]["level"] == "root_sw"

    # (a) the refitted params flow through the fingerprints: the same
    # request resolves to a NEW key and the old entry is never hit
    bp_new = svc.get_bucket_plan([("data", 8)], float(1 << 18))
    assert bp_new.key != bp_old.key
    assert bp_new.source == "cold"
    assert svc.cache.stats.misses > misses_before_refit

    # (c) fresh schedules, lowered under the refitted model — the stale
    # CompiledSchedule is unreachable (identity assertion)
    sched_new = bp_new.axis_plans[0].schedule
    assert sched_new is not None and sched_new is not sched_old


def test_closed_loop_converges_and_never_executes_stale_schedules():
    """Acceptance: a training loop started with deliberately
    mis-calibrated GenModelParams observes measured costs, refits,
    replans and converges — post-refit predicted axis cost tracks
    measured within 10%, and no stale CompiledSchedule is ever executed
    after the swap (schedule identity)."""
    import dataclasses

    from repro.core.cost_model import PAPER_TABLE5
    from repro.planner.service import PlannerService, RefitPolicy

    true = PAPER_TABLE5
    wrong = dict(true)
    wrong["root_sw"] = dataclasses.replace(
        true["root_sw"], alpha=true["root_sw"].alpha / 3,
        beta=true["root_sw"].beta / 6)
    svc = PlannerService(params=wrong, refit_policy=RefitPolicy(
        min_samples=6, drift_threshold=0.15, cooldown=6))
    measure = _drifted_cluster(true, svc)

    sizes = [(8, 1e6), (8, 4e6), (4, 1e6), (8, 1.6e7), (4, 4e6),
             (8, 2e6), (8, 8e6), (4, 2e6)]
    executed = []          # (schedule, params fingerprint at execution)
    refit_at = None
    for step in range(4 * len(sizes)):
        n, size = sizes[step % len(sizes)]
        resp, meas = measure(n, size)
        # "execute" the schedule this step: record its identity
        executed.append(resp.schedule)
        out = svc.observe("root_sw", n, size, meas,
                          predicted=resp.predicted_time, key=resp.key)
        if out["refit"] and refit_at is None:
            refit_at = len(executed)
            stale = set(map(id, executed))
    assert refit_at is not None, "loop never refit"

    # no stale CompiledSchedule executed after the swap
    post_swap = executed[refit_at:]
    assert post_swap, "no steps ran after the refit"
    assert all(id(s) not in stale for s in post_swap)

    # converged: post-refit predictions track measurements within 10%
    for n, size in sizes:
        resp, meas = measure(n, size)
        assert abs(resp.predicted_time - meas) / meas < 0.10, \
            f"post-refit divergence at n={n} S={size}"
