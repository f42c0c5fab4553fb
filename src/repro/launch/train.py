"""Training drivers.

Two engines:

* **auto** — jax.jit + NamedSharding (FSDP × TP × pod-DP). XLA SPMD inserts
  every collective. This is the baseline engine every dry-run cell lowers
  with.
* **manual** — shard_map over the DP axes ('pod', 'data'); parameters are
  ZeRO-3 sharded (flat shards per leaf), gathered with a *plan-selected*
  AllGather and gradients reduced with a *plan-selected* ReduceScatter —
  ring / rhd / cps / hcps per core.sync's GenModel pricing, or, with
  sync="plan", the GenTree Plan IR itself lowered to a compiled schedule
  (core.lower, DESIGN.md §8) and executed round-for-round. This is the
  paper's technique as a first-class training feature: GenTree decides the
  collective schedule, the engine executes it.

`python -m repro.launch.train --arch <id> --steps N` runs a reduced-config
training loop on the local devices (examples/tests); `--layers L` runs the
config at its published widths with its depth cut to L layers instead.
Full-size configs are exercised via launch.dryrun.
"""
from __future__ import annotations

import dataclasses
import math
import sys
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import collectives
from repro.core.sync import AxisPlan, SyncConfig, plan_axes_gentree
from repro.models.registry import ModelAPI
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.runtime.trace import scope
from . import sharding as shr
from .compile_cache import enable_compile_cache
from .mesh import axis_sizes, dp_axes, make_mesh


def _note_sync_fallback(what: str, err) -> None:
    """A planned sync path replaced by a simpler one: counted, and named
    on stderr, so a run that fell back never passes for one that did not."""
    from repro.runtime.metrics import default_metrics
    default_metrics().counter(
        "sync_fallbacks_total",
        "planned sync paths replaced by a simpler one").inc()
    print(f"fallback: {what} ({err!r})", file=sys.stderr, flush=True)


def _attention_paths() -> str:
    """The trace-time count of attention calls by the path they can take
    (`models/layers.py`: splash kernels on a TPU, or XLA)."""
    from repro.models.layers import attention_path_metric
    from repro.runtime.metrics import default_metrics
    snap = default_metrics().snapshot()
    counts = " ".join(
        f"{p}={snap.get(attention_path_metric(p), {}).get('value', 0):g}"
        for p in ("kernel", "xla"))
    return f"attention paths (calls traced): {counts}"


# ---------------------------------------------------------------------------
# auto engine (pjit)
# ---------------------------------------------------------------------------
def make_train_step(api: ModelAPI, mesh: Mesh,
                    opt_cfg: AdamWConfig = AdamWConfig(), *,
                    donate: bool = True, fsdp: bool = True,
                    act_hook=None):
    """Returns (jitted_step, state_shardings_fn, batch_shardings_fn).
    fsdp=False → ZeRO-1 (params replicated over DP, moments sharded)."""
    from repro.models import actsharding

    def step(state, batch):
        actsharding.set_hook(act_hook or actsharding.batch_dp_hook(mesh),
                             mesh)
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch))(state["params"])
        params, opt, gnorm = adamw_update(state["params"], grads,
                                          state["opt"], opt_cfg)
        return ({"params": params, "opt": opt},
                {"loss": loss, "gnorm": gnorm})

    def state_shardings(state_shape):
        p_spec = shr.params_specs(state_shape["params"], mesh, fsdp=fsdp)
        return shr.to_named(
            {"params": p_spec,
             "opt": shr.opt_specs(state_shape["opt"], p_spec, mesh)},
            mesh)

    def batch_shardings(batch_shape):
        return shr.to_named(shr.batch_specs(batch_shape, mesh), mesh)

    def jitted(state_shape, batch_shape):
        ss = state_shardings(state_shape)
        bs = batch_shardings(batch_shape)
        ms = shr.to_named({"loss": P(), "gnorm": P()}, mesh)
        return jax.jit(step, in_shardings=(ss, bs),
                       out_shardings=(ss, ms),
                       donate_argnums=(0,) if donate else ())

    return jitted, state_shardings, batch_shardings


# ---------------------------------------------------------------------------
# manual engine (shard_map, ZeRO-3 with plan-selected collectives)
# ---------------------------------------------------------------------------
def _flat_shard(x: jax.Array, n: int, idx: jax.Array) -> jax.Array:
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return jax.lax.dynamic_slice_in_dim(flat, idx * (flat.size // n),
                                        flat.size // n)


def shard_params_zero3(params: Any, mesh: Mesh) -> Any:
    """Host-side: split every leaf into flat per-DP-rank shards, placed with
    P(dp) on a leading shard axis."""
    dp = dp_axes(mesh)
    sizes = axis_sizes(mesh)
    n = 1
    for a in dp:
        n *= sizes[a]

    def split(x):
        flat = jnp.asarray(x).reshape(-1)
        pad = (-flat.size) % n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        out = flat.reshape(n, -1)
        # P(dp), not P(dp, None): the step's outputs carry the normalized
        # spec, and an input spec that differs in form recompiles step 1
        return jax.device_put(out, NamedSharding(mesh, P(dp)))

    return jax.tree.map(split, params)


def _gather_leaf(shard: jax.Array, shape, dtype, plans: Sequence[AxisPlan]):
    flat = shard
    for pl in plans:
        # collectives.all_gather inverts _scatter_leaf's reduce_scatter
        # per strategy — including the hcps un-reorder back to native
        # holder order (gathering with all_gather_hcps directly permutes
        # the result, since reduce_scatter hands back natural shards)
        flat = collectives.all_gather(flat, pl.axis, pl.strategy,
                                      factors=pl.factors,
                                      schedule=pl.schedule)
    n = 1
    for s in shape:
        n *= s
    return flat.reshape(-1)[:n].reshape(shape).astype(dtype)


def _scatter_leaf(full: jax.Array, plans: Sequence[AxisPlan]):
    flat = full.reshape(-1)
    for pl in reversed(plans):
        flat = collectives.reduce_scatter(flat, pl.axis, pl.strategy,
                                          factors=pl.factors,
                                          schedule=pl.schedule)
    return flat


def make_manual_train_step(api: ModelAPI, mesh: Mesh,
                           opt_cfg: AdamWConfig = AdamWConfig(), *,
                           sync: SyncConfig = SyncConfig(strategy="gentree"),
                           planner=None):
    """ZeRO-3 shard_map engine. Parameter AllGather and gradient
    ReduceScatter run the GenModel-selected plan per mesh level (intra-pod
    first, cross-pod second — the paper's hierarchical structure).

    Plan lookups route through the PlannerService (repro.planner): plans
    are resolved once at engine-build (trace) time, and the fingerprinted,
    size-bucketed cache pays off across engine rebuilds and — with
    $REPRO_PLAN_CACHE set — across process restarts, which skip the
    GenModel search entirely. Pass `planner` to use a calibrated or
    skew-aware service instead of the process-wide default."""
    dp = dp_axes(mesh)
    sizes = axis_sizes(mesh)
    axes = [(a, sizes[a]) for a in dp if sizes[a] > 1]
    ndp = 1
    for _, s in axes:
        ndp *= s
    shapes = api.params_spec()
    leaf_shapes = jax.tree.map(lambda l: (l.shape, l.dtype), shapes,
                               is_leaf=lambda x: hasattr(x, "shape"))

    def plans_for(size_floats: float) -> list[AxisPlan]:
        from repro.core.sync import resolve_axis_plans
        if sync.strategy == "auto":
            return [AxisPlan(a, "psum") for a, _ in axes]
        if planner is not None and sync.strategy == "gentree":
            return planner.get_axis_plans(axes, size_floats,
                                          params=sync.params)
        if planner is not None and sync.strategy == "plan":
            from repro.core.sync import axis_level
            out = []
            for i, (a, n) in enumerate(axes):
                resp = planner.get_axis_executable(
                    a, n, size_floats, level=axis_level(i),
                    params=sync.params)
                sched = resp.schedule
                if getattr(sync, "guard", True):
                    from repro.core.lower import guard_schedule
                    sched = guard_schedule(
                        sched,
                        telemetry=getattr(planner, "telemetry", None))
                out.append(AxisPlan(a, "plan", schedule=sched,
                                    predicted=resp.predicted_time))
            return out
        # gentree/plan route through the process-wide PlannerService inside
        # resolve_axis_plans; only an explicit override needs handling here.
        return resolve_axis_plans(axes, sync, size_floats)

    flat_sd, sd_treedef = jax.tree.flatten(
        jax.tree.map(lambda l: (tuple(l.shape), l.dtype), shapes,
                     is_leaf=lambda x: hasattr(x, "shape")),
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    # f32-equivalent size of the full parameter set (total bytes / 4), so
    # bf16/f16 models are priced at their real data volume rather than at
    # raw element counts in float32 units
    total_f32_equiv = sum(
        int(math.prod(sd[0])) * jnp.dtype(sd[1]).itemsize
        for sd in flat_sd) / 4.0

    def bucket_plan_for():
        """Bucketed ZeRO-3 halves (DESIGN.md §9): one schedule launch per
        dtype-homogeneous bucket instead of per leaf, bucket size the
        GenModel sweep argmin. Single-DP-axis layout only (the bucket's
        row layout must match the host-side shard split); multi-axis
        meshes and schedules without canonical RS/AG halves fall back to
        the per-leaf path."""
        if (sync.strategy != "plan" or sync.bucket_bytes == 0
                or len(axes) != 1):
            return None
        svc = planner
        if svc is None:
            from repro.planner.service import default_service
            svc = default_service()
        from repro.core.bucketing import BucketConfig
        from repro.core.lower import LoweringError
        try:
            bp = svc.get_bucket_plan(
                axes, total_f32_equiv or 1.0, params=sync.params,
                config=BucketConfig(
                    bucket_bytes=sync.bucket_bytes,
                    pipeline=sync.pipeline,
                    precision=getattr(sync, "precision", None),
                    tolerance=getattr(sync, "tolerance", None)))
        except LoweringError as e:
            _note_sync_fallback("bucketed ZeRO-3 halves -> per-leaf "
                                "collectives", e)
            return None
        cs = bp.axis_plans[0].schedule if bp.axis_plans else None
        if cs is None or not cs.blocks_per_shard:
            return None
        if getattr(sync, "guard", True):
            # the zero3 bucketed halves launch plan.schedule directly,
            # so the guard (DESIGN.md §12) must wrap here too — the
            # memoized wrapper keeps demotion sticky across retraces
            import dataclasses as _dc
            from repro.core.lower import guard_schedule
            tele = getattr(svc, "telemetry", None)
            bp = _dc.replace(bp, axis_plans=[
                _dc.replace(pl, schedule=guard_schedule(pl.schedule,
                                                        telemetry=tele))
                if pl.schedule is not None else pl
                for pl in bp.axis_plans])
        return bp

    # Expert-parallel MoE (ISSUE 9 tentpole): when the model routes
    # experts and they shard evenly over the leaf DP axis, the MoE layer
    # dispatches with AllToAll inside this engine's shard_map — executed
    # from a lowered family="all_to_all" plan under strategy="plan"
    # (guarded like every other planned collective), lax.all_to_all
    # otherwise.
    ep_axis, ep_n = (axes[0] if axes else (None, 1))
    use_ep = (getattr(api.cfg, "n_experts", 0) > 1 and ep_axis is not None
              and ep_n > 1 and api.cfg.n_experts % ep_n == 0)
    ep_sched = None
    if use_ep and sync.strategy == "plan":
        svc = planner
        if svc is None:
            from repro.planner.service import default_service
            svc = default_service()
        from repro.core.lower import LoweringError
        try:
            resp = svc.get_family_executable(
                "all_to_all", ep_axis, ep_n, total_f32_equiv or 1.0,
                params=sync.params)
            ep_sched = resp.schedule
            if ep_sched is not None and getattr(sync, "guard", True):
                from repro.core.lower import guard_schedule
                ep_sched = guard_schedule(
                    ep_sched, telemetry=getattr(svc, "telemetry", None))
        except LoweringError as e:
            _note_sync_fallback("planned expert AllToAll -> "
                                "lax.all_to_all", e)
            ep_sched = None

    def step(state, batch):
        from repro.models import actsharding
        actsharding.set_hook(None)    # shard_map bodies are fully manual

        def inner(p_shards, opt, batch_local):
            from repro.core import bucketing
            total_size = sum(
                float(jnp.size(s)) for s in jax.tree.leaves(p_shards)) or 1.0
            bplan = bucket_plan_for()
            plans = None if bplan is not None else plans_for(total_size)
            from repro.runtime.metrics import default_metrics
            default_metrics().counter(
                "zero3_bucketed_traces_total" if bplan is not None
                else "zero3_per_leaf_traces_total",
                "ZeRO-3 steps traced on this sync path").inc()

            flat_shards = jax.tree.leaves(p_shards)
            with scope("zero3/gather"):
                if bplan is not None:
                    gathered = bucketing.zero3_gather_bucketed(
                        [s[0] for s in flat_shards], flat_sd,
                        bplan.axis_plans[0], bplan.bucket_bytes, ndp)
                else:
                    gathered = [
                        _gather_leaf(s[0], sd[0], sd[1], plans)
                        for s, sd in zip(flat_shards, flat_sd)]
            params = jax.tree.unflatten(jax.tree.structure(p_shards),
                                        gathered)
            if use_ep:
                from repro.core import sync as sync_mod
                with sync_mod.expert_parallel(ep_axis, ep_n, ep_sched):
                    loss, grads = jax.value_and_grad(
                        lambda p: api.loss_fn(p, batch_local, remat=True,
                                              moe_dispatch="ep"))(params)
            else:
                loss, grads = jax.value_and_grad(
                    lambda p: api.loss_fn(p, batch_local,
                                          remat=True))(params)
            # mean over DP shards happens inside the reduce; rescale
            with scope("zero3/scatter"):
                if bplan is not None:
                    rows = bucketing.zero3_scatter_bucketed(
                        jax.tree.leaves(grads), bplan.axis_plans[0],
                        bplan.bucket_bytes, ndp,
                        reverse=getattr(sync, "backward_overlap", False))
                    g_shards = jax.tree.unflatten(
                        jax.tree.structure(grads),
                        [(r / ndp)[None] for r in rows])
                else:
                    g_shards = jax.tree.map(
                        lambda g: (_scatter_leaf(g, plans) / ndp)[None],
                        grads)
            loss = jax.lax.pmean(loss, tuple(a for a, _ in axes))
            new_p, new_o, gn = adamw_update(p_shards, g_shards, opt, opt_cfg)
            gn = jax.lax.pmean(gn, tuple(a for a, _ in axes))
            return new_p, new_o, loss, gn

        from jax import shard_map
        spec_shard = jax.tree.map(lambda _: P(dp, None), state["params"])
        spec_opt = {"m": spec_shard, "v": spec_shard, "step": P()}
        bspec = shr.batch_specs(batch, mesh)
        new_p, new_o, loss, gn = shard_map(
            inner, mesh=mesh,
            in_specs=(spec_shard, spec_opt, bspec),
            out_specs=(spec_shard, spec_opt, P(), P()),
            check_vma=False)(state["params"], state["opt"], batch)
        return ({"params": new_p, "opt": new_o},
                {"loss": loss, "gnorm": gn})

    return jax.jit(step, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# driver (reduced-config local training; examples import run_training)
# ---------------------------------------------------------------------------
def observe_sync_probe(svc, mesh, axes, size_floats, on_log=print, *,
                       repeats: int = 3):
    """Measure each live DP axis's compiled schedule on the real mesh and
    feed the timings into the planner's online loop (DESIGN.md §10).

    The training step is one fused jit program — the collective's wall
    time cannot be carved out of it — so the measurement instrument is a
    *probe*: the axis's lowered `CompiledSchedule` (the exact schedule
    the step executes) runs alone under shard_map on the live mesh, and
    its measured median wall time is paired with the GenModel prediction
    via `PlannerService.observe`. Each axis is probed at TWO sizes (the
    requested size and a quarter of it): the refit trigger refuses a
    rank-deficient fit from one repeated (n, size) point
    (`PlannerService._sample_diversity`), so a train-only deployment
    must deposit size diversity or its accumulated drift could never
    refit. A single run only deposits a handful of samples (below any
    refit policy's `min_samples`), so short smoke runs never perturb
    the pricing basis."""
    import time

    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.core.sync import axis_level

    out = []
    big = max(float(size_floats), 4.0)
    for i, (a, n) in enumerate(axes):
        if n <= 1:
            continue
        for size in (big, big / 4.0):
            try:
                resp = svc.get_axis_executable(a, int(n), size,
                                               level=axis_level(i))
                sched = resp.schedule
                probe = jnp.ones((int(n), max(int(size), 1)), jnp.float32)
                # jitted: an un-jitted shard_map re-traces per call,
                # which would time the tracer instead of the collective
                f = jax.jit(shard_map(
                    lambda v, s=sched, ax=a: s.allreduce(v[0], ax)[None],
                    mesh=mesh, in_specs=P(a), out_specs=P(a)))
                jax.block_until_ready(f(probe))          # warm/compile
                ts = []
                for _ in range(repeats):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(probe))
                    ts.append(time.perf_counter() - t0)
                measured = sorted(ts)[len(ts) // 2]
                # no predicted= override: the probe size rarely lands on
                # a geometric cache bucket, and resp.predicted_time is
                # priced at the SNAPPED size — observe's default
                # re-prices the plan at the exact executed size, so the
                # residual compares like with like instead of carrying a
                # constant bucket-ratio bias
                obs = svc.observe(axis_level(i), int(n), size, measured,
                                  key=resp.key)
                out.append(obs)
                on_log(f"planner: axis {a} sync probe "
                       f"({int(size)} floats) {measured * 1e3:.3f} ms "
                       f"(predicted {obs['predicted'] * 1e3:.3f} ms, "
                       f"drift {obs['drift']:.2f}"
                       + (", refit" if obs["refit"] else "") + ")")
            except Exception as e:   # advisory — never fail training
                _note_sync_fallback(f"sync probe for axis {a} skipped", e)
    return out


@dataclasses.dataclass
class TrainConfig:
    arch: str = "stablelm-12b"
    # None: the smoke-shrunk config of the same family (CPU tests).
    # L: the published config at its published widths, depth cut to L
    # layers.
    layers: int | None = None
    steps: int = 50
    seq_len: int = 128
    global_batch: int = 8
    engine: str = "auto"            # auto | manual
    sync: str = "auto"         # auto|psum|ring|rhd|cps|hcps|gentree|plan
    # backward-overlapped bucket issuance (DESIGN.md §15): reverse-layer
    # readiness order + the planner's merged RS/AG launch when its
    # contended argmin picked "merged"; False restores forward order
    backward_overlap: bool = True
    lr: float = 1e-3
    ckpt_dir: str | None = None
    ckpt_every: int = 25
    seed: int = 0
    log_every: int = 10
    # feed measured sync timings back into the planner's online loop
    # (probe after training; per-step wall times into the telemetry ring)
    observe_sync: bool = True
    # when set, enable the process-wide tracer for the run and export a
    # Chrome-trace JSON (load in chrome://tracing or ui.perfetto.dev) of
    # every recorded span — planner, lowering, bucketing and train steps
    trace_path: str | None = None
    # when set, export the process-wide metrics registry (JSON snapshot +
    # sibling .prom text file) at the end of the run
    metrics_path: str | None = None
    # chaos mode (DESIGN.md §12): a `FaultPlan.parse` spec string (e.g.
    # "seed=7,steps=200,link_degrade=0.01,payload_corrupt=0.05") arms a
    # deterministic fault injector for the run; None defers to any
    # $REPRO_FAULT_PLAN / surrounding FaultInjector context
    fault_plan: str | None = None


def run_training(tc: TrainConfig, mesh: Mesh | None = None,
                 on_log=print) -> dict:
    from repro.configs import get_config
    from repro.data import DataConfig, SyntheticLM
    from repro.models.config import smoke_config
    from repro.models.registry import build
    from repro.checkpoint import CheckpointManager
    from repro.runtime import FaultTolerantLoop

    published = get_config(tc.arch)
    cfg = (smoke_config(published) if tc.layers is None
           else dataclasses.replace(published, n_layers=tc.layers))
    api = build(cfg)
    n_params = sum(math.prod(leaf.shape)
                   for leaf in jax.tree.leaves(api.params_spec()))
    on_log(f"model: {cfg.name} [{cfg.family}] d_model={cfg.d_model} "
           f"d_ff={cfg.d_ff} heads={cfg.n_heads}x{cfg.head_dim} "
           f"vocab={cfg.vocab} layers={cfg.n_layers}/{published.n_layers} "
           f"params={n_params:,}")
    mesh = mesh or make_mesh((len(jax.devices()), 1), ("data", "model"))

    dcfg = DataConfig(
        vocab=cfg.vocab, seq_len=tc.seq_len, global_batch=tc.global_batch,
        seed=tc.seed,
        embed_dim=cfg.d_model if cfg.embeds_input else 0,
        frames=32 if cfg.family == "audio" else 0)
    data = SyntheticLM(dcfg)
    opt_cfg = AdamWConfig(lr=tc.lr)

    params = api.init_params(jax.random.PRNGKey(tc.seed))
    if tc.engine == "manual":
        # drop the unsharded copy before the optimizer state exists: at
        # published widths it is a whole model's worth of device memory
        params = shard_params_zero3(params, mesh)
        # the moments are made in place on their shards: made on the
        # default device, a model's f32 moments would all land on chip 0
        shard = jax.tree.map(lambda x: x.sharding, params)
        opt = jax.jit(adamw_init, out_shardings={
            "m": shard, "v": shard,
            "step": NamedSharding(mesh, P())})(params)
    else:
        opt = adamw_init(params)
    state = {"params": params, "opt": opt}
    del params, opt

    b0 = jax.tree.map(jnp.asarray, data.batch_at(0))
    if tc.engine == "manual":
        step_fn = make_manual_train_step(
            api, mesh, opt_cfg, sync=SyncConfig(
                strategy=tc.sync,
                backward_overlap=tc.backward_overlap))
        # compile ahead of the first step (the jit call reuses this
        # executable) so the device memory the step needs is on record
        mem = step_fn.lower(state, b0).compile().memory_analysis()
        if mem is not None:
            gib = 1 << 30
            on_log(f"compiled step per device: args "
                   f"{mem.argument_size_in_bytes / gib:.2f} GiB, temps "
                   f"{mem.temp_size_in_bytes / gib:.2f} GiB, outputs "
                   f"{mem.output_size_in_bytes / gib:.2f} GiB, aliased "
                   f"{mem.alias_size_in_bytes / gib:.2f} GiB")
    else:
        jitted, ss_fn, bs_fn = make_train_step(api, mesh, opt_cfg)
        step_fn = jitted(jax.eval_shape(lambda: state),
                         jax.eval_shape(lambda: b0))

    losses = []
    # the process-wide telemetry hub: the same rings the straggler
    # watchdog and the planner's drift detector read (DESIGN.md §10)
    from repro.runtime.telemetry import default_telemetry
    tele = default_telemetry() if tc.observe_sync else None

    from repro.runtime.metrics import default_metrics
    from repro.runtime.trace import default_tracer
    tracer = default_tracer()
    if tc.trace_path:
        tracer.enabled = True
    step_hist = default_metrics().histogram(
        "train_step_seconds", "wall time per training step",
        buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0))

    step_seconds = []

    def one_step(state, step):
        import time as _time
        t0 = _time.perf_counter()
        with tracer.span("train/step", step=step):
            batch = jax.tree.map(jnp.asarray, data.batch_at(step))
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])     # waits for the step
        step_seconds.append(_time.perf_counter() - t0)
        step_hist.observe(step_seconds[-1])
        if not losses:                  # the step is traced and compiled
            on_log(_attention_paths())
        if step % tc.log_every == 0:
            on_log(f"step {step:5d}  loss {loss:.4f}  "
                   f"gnorm {float(metrics['gnorm']):.3f}")
        losses.append(loss)
        return state

    import contextlib
    injector = None
    inj_scope = contextlib.nullcontext()
    if tc.fault_plan:
        from repro.runtime.faults import FaultInjector, FaultPlan
        injector = FaultInjector(FaultPlan.parse(tc.fault_plan))
        # entering the scope arms the process-global injector, so
        # GuardedSchedule launches see the payload-corruption events too
        inj_scope = injector
        on_log(f"chaos: armed fault plan {injector.plan.key()} "
               f"({len(injector.plan.events)} events)")
    if tc.ckpt_dir:
        # hand the loop the planner so injected link faults replan
        # through the service's health map (DESIGN.md §12)
        loop_planner = None
        if tc.engine == "manual" and tc.sync in ("gentree", "plan"):
            from repro.planner.service import default_service
            loop_planner = default_service()
        mgr = CheckpointManager(tc.ckpt_dir, keep=2)
        loop = FaultTolerantLoop(one_step, state, mgr,
                                 ckpt_every=tc.ckpt_every,
                                 telemetry=tele,
                                 planner=loop_planner,
                                 injector=injector)
        with inj_scope:
            state = loop.run(tc.steps)
        if injector is not None:
            on_log(f"chaos: injector fired {injector.stats()['fired']}")
    else:
        import time
        with inj_scope:
            for s in range(tc.steps):
                t0 = time.perf_counter()
                state = one_step(state, s)
                if tele is not None:
                    tele.record("train/step", time.perf_counter() - t0)

    if tc.engine == "manual" and tc.sync in ("gentree", "plan"):
        # Plans resolve once at trace time, so a fresh process shows one
        # miss per axis-plan request; hits appear on engine rebuilds and
        # on warm restarts via $REPRO_PLAN_CACHE.
        from repro.planner.service import default_service
        svc = default_service()
        if tc.observe_sync and tc.sync == "plan":
            # close the measurement loop: execute each DP axis's
            # compiled schedule alone on the live mesh and feed the
            # measured timings into the drift detector. The axis list is
            # filtered exactly as make_manual_train_step builds it —
            # size-1 axes dropped BEFORE level indexing — so the probe
            # observes the same Table-5 level class the step priced.
            dp = dp_axes(mesh)
            sizes_by_axis = axis_sizes(mesh)
            live = [(a, sizes_by_axis[a]) for a in dp
                    if sizes_by_axis[a] > 1]
            if live:
                probe_floats = min(
                    sum(float(x.size) for x in
                        jax.tree.leaves(state["params"])) or 1.0,
                    65536.0)
                observe_sync_probe(svc, mesh, live, probe_floats, on_log)
        st = svc.stats()
        cs = st["cache"]
        on_log(f"planner cache: {st['entries']} entries, "
               f"{cs['hits']} hits / {cs['misses']} misses"
               + (f", {cs['disk_loads']} loaded from disk"
                  if cs["disk_loads"] else ""))
        if st["refits"]:
            on_log(f"planner: {len(st['refits'])} online refit(s): "
                   + ", ".join(f"{r['level']} (drift {r['drift']:.2f})"
                               for r in st["refits"]))

    if tc.trace_path:
        tracer.export_chrome(tc.trace_path)
        on_log(f"trace: {len(tracer.spans)} spans -> {tc.trace_path}")
    if tc.metrics_path:
        default_metrics().export(tc.metrics_path)
        on_log(f"metrics -> {tc.metrics_path}")

    # step_seconds[0] includes compiling the step for the auto engine; the
    # manual engine compiled it before the first step
    return {"state": state, "losses": losses, "step_seconds": step_seconds}


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-12b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--engine", default="auto")
    ap.add_argument("--sync", default="auto")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export a Chrome-trace JSON of the run")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="export a metrics snapshot (JSON + .prom)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="arm a deterministic chaos fault plan, e.g. "
                         "'seed=7,steps=200,payload_corrupt=0.05'")
    ap.add_argument("--layers", type=int, default=None,
                    help="run the config at its published widths with "
                         "this many layers (default: a smoke-shrunk "
                         "config of the same family)")
    args = ap.parse_args()
    enable_compile_cache()
    out = run_training(TrainConfig(
        arch=args.arch, layers=args.layers, steps=args.steps,
        engine=args.engine,
        sync=args.sync, seq_len=args.seq_len, global_batch=args.batch,
        ckpt_dir=args.ckpt_dir, trace_path=args.trace,
        metrics_path=args.metrics, fault_plan=args.faults))
    print(f"final loss: {out['losses'][-1]:.4f}")


if __name__ == "__main__":
    main()
