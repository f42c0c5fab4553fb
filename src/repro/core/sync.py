"""Gradient synchronization strategies — where GenTree meets the trainer.

A SyncConfig selects how DP gradients are reduced across the mesh's
data-parallel axes. `strategy="gentree"` builds the TPU-pod tree topology,
prices every plan type per level with GenModel (TPU_V5E parameters), and
picks the winner — typically hierarchical CPS with fan-ins capped by the
per-level incast threshold w_t, exactly the paper's δ/ε trade-off.

Used inside shard_map train steps (manual engine) and by the launcher to
pick mesh-axis factorizations for the pjit (auto) engine.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from . import collectives
from .cost_model import GenModelParams, TPU_V5E, best_flat_plan


@dataclasses.dataclass(frozen=True)
class AxisPlan:
    axis: str
    strategy: str                   # psum | ring | rhd | cps | hcps | plan
    factors: tuple[int, ...] | None = None
    # strategy == "plan": the lowered GenTree schedule to execute
    # (core.lower.CompiledSchedule; compared/hashed by identity)
    schedule: object | None = None
    # modeled cost of this axis's plan at the priced size (seconds) —
    # what the runtime pairs with measured timings when it feeds the
    # online loop (PlannerService.observe, DESIGN.md §10)
    predicted: float | None = None


@dataclasses.dataclass(frozen=True)
class SyncConfig:
    """strategy: auto|psum|ring|rhd|cps|hcps|gentree|plan per DP axis.
    "gentree" picks a flat plan-type label per axis; "plan" lowers the
    GenTree Plan IR itself and executes its compiled schedule — bucketed
    and pipelined by default (core.bucketing, DESIGN.md §9):
    bucket_bytes=None lets GenModel pick the bucket size (the sweep
    argmin), an explicit value pins it, and 0 disables bucketing
    (legacy per-leaf execution). pipeline=False runs buckets
    back-to-back instead of overlapping AG(k) with RS(k+1)."""
    strategy: str = "auto"
    factors: tuple[int, ...] | None = None   # for explicit hcps
    compress: str | None = None              # None | "int8"
    params: dict[str, GenModelParams] | None = None
    bucket_bytes: int | None = None          # None=auto | 0=off | fixed
    pipeline: bool = True                    # double-buffer RS/AG halves
    # Backward-overlapped issuance (DESIGN.md §15): issue buckets in
    # reverse-layer readiness order (backward produces last-layer grads
    # first) and fuse RS(k)/AG(k−1) into one merged launch when the
    # planner's contended argmin picked "merged". False restores
    # forward-order sequential issuance.
    backward_overlap: bool = True
    # Wrap executed schedules in core.lower.GuardedSchedule (retry +
    # flat-psum fallback ladder, DESIGN.md §12). Off ⇒ raw schedules.
    guard: bool = True
    # Wire precision for the planned path (DESIGN.md §13). `precision`
    # pins a PRECISIONS name ("f32"|"bf16"|"fp8"|"int8"); None lets the
    # bucket-plan sweep argmin over precisions allowed by `tolerance`
    # (max relative gradient error the caller accepts). tolerance=None
    # means no lossy consent: the sweep stays lossless and a pinned
    # lossy precision whose budget exceeds a float tolerance clamps to
    # f32 (cost_model.resolve_precision).
    precision: str | None = None
    tolerance: float | None = None


# Table-5 class per mesh-axis position: the leaf axis rides the pod fabric
# (ICI → "root_sw" pricing), every outer axis the cross-pod DCI.
AXIS_LEVELS = ("root_sw",) + ("cross_dc",) * 8


def axis_level(i: int) -> str:
    return AXIS_LEVELS[min(i, len(AXIS_LEVELS) - 1)]


def level_switch_topo(n: int, params: dict[str, GenModelParams],
                      level: str):
    """Single-switch stand-in for a mesh axis at a Table-5 level class:
    one switch, n servers whose uplink bandwidth realizes the level's β
    (seconds per 4-byte unit → bytes/s), pricing α/γ/δ/ε/w_t coming from
    the params table. The ONE synthesis shared by axis pricing
    (`plan_axes_gentree`) and axis execution
    (`PlannerService.get_axis_executable`) — the executed plan must be
    the plan the model priced."""
    from .topology import single_switch
    p = params.get(level, params["server"])
    bw = 4.0 / p.beta if p.beta > 0 else 1e18
    return single_switch(int(n), bw=bw, lat=0.0, level=level)


def plan_axes_gentree(axes: Sequence[tuple[str, int]], size_floats: float,
                      params: dict[str, GenModelParams] | None = None, *,
                      engine: str | None = None,
                      gentree_kwargs: dict | None = None) -> list[AxisPlan]:
    """Per-level plan selection for a hierarchical mesh.

    axes: [(axis_name, size), ...] ordered leaf-level first (e.g.
    [("data", 16), ("pod", 2)]). Level 0 prices with pod-internal (ICI)
    parameters, outer levels with the cross-pod (DCI) parameters — the
    TPU analogue of the paper's Table-5 level classes.

    With default `engine`/`gentree_kwargs` each axis is priced by the
    GenModel closed forms (`best_flat_plan`). When either is configured
    (a PlannerService built with engine="reference"/"fast" or custom
    gentree_kwargs), the axis is priced by running GenTree itself on the
    equivalent single-switch topology — one switch, n servers whose link
    bandwidth realizes the level's β — with exactly that engine and those
    kwargs, so service configuration reaches cold axis pricing instead of
    being silently dropped.
    """
    params = params or TPU_V5E
    gkw = dict(gentree_kwargs or {})
    use_gentree = engine is not None or bool(gkw)
    out: list[AxisPlan] = []
    for i, (name, n) in enumerate(axes):
        lvl = axis_level(i)
        p = params[lvl]
        # the γ/δ terms always price at the chip ("server") level
        srv = params["server"]
        p = dataclasses.replace(p, gamma=srv.gamma, delta=srv.delta)
        if n == 1:
            continue
        if use_gentree:
            from .gentree import gentree as run_gentree
            topo = level_switch_topo(n, {lvl: p, "server": srv}, lvl)
            res = run_gentree(topo, size_floats,
                              params={lvl: p, "server": srv},
                              engine=engine, **gkw)
            dec = res.decisions[topo.name]
            kind = "cps" if dec.algo == "acps" else dec.algo
            fac = dec.factors
            cost = dec.cost
        else:
            kind, fac, cost = best_flat_plan(n, size_floats, p)
        out.append(AxisPlan(name, kind, tuple(fac) if fac else None,
                            predicted=float(cost)))
    return out


def resolve_axis_plans(axes: Sequence[tuple[str, int]], cfg: "SyncConfig",
                       size_floats: float) -> list[AxisPlan]:
    """Per-axis plan resolution shared by the gradient-sync and ZeRO-3
    engines. hcps factors are validated per axis (explicit factors only
    apply where they multiply to the axis size; otherwise the first valid
    factorization, degrading to cps on prime axes)."""
    import math as _math
    from .plans import factorizations

    if cfg.strategy == "gentree":
        # Route through the planner service: lookups are fingerprinted,
        # size-bucketed and LRU-cached (repro.planner, DESIGN.md §5), so
        # repeated train steps don't re-price the mesh. Lazy import —
        # planner depends on this module.
        from repro.planner.service import default_service
        return default_service().get_axis_plans(axes, size_floats,
                                                params=cfg.params)
    if cfg.strategy == "plan":
        # Execute the GenTree Plan IR itself: per axis, the service
        # generates (or cache-hits) the plan AND its lowered schedule
        # (DESIGN.md §8); the returned AxisPlan carries the compiled
        # schedule for collectives.allreduce/reduce_scatter to run.
        # Pricing matches plan_axes_gentree: leaf axis at "root_sw",
        # outer axes at "cross_dc", cfg.params honoured.
        from repro.planner.service import default_service
        svc = default_service()
        wire = None
        pname = getattr(cfg, "precision", None)
        if pname is not None:
            from .cost_model import resolve_precision
            prec = resolve_precision(pname, getattr(cfg, "tolerance", None))
            wire = prec if prec.name != "f32" else None
        out = []
        # level index counts the ORIGINAL axis position (n==1 axes are
        # skipped but still occupy their mesh level), exactly as
        # plan_axes_gentree enumerates — same axis, same Table-5 class.
        for i, (a, n) in enumerate(axes):
            if n <= 1:
                continue
            resp = svc.get_axis_executable(a, n, size_floats,
                                           level=axis_level(i),
                                           params=cfg.params)
            sched = resp.schedule
            if wire is not None:
                # wire-bound copy (fresh object): its guard wrapper
                # memoizes separately from the full-precision users of
                # the same cached schedule (DESIGN.md §13)
                sched = sched.with_wire(wire)
            if getattr(cfg, "guard", True):
                from .lower import guard_schedule
                sched = guard_schedule(
                    sched, telemetry=getattr(svc, "telemetry", None))
            out.append(AxisPlan(a, "plan", schedule=sched,
                                predicted=resp.predicted_time))
        return out

    def axis_plan(a: str, n: int) -> AxisPlan:
        if cfg.strategy != "hcps":
            return AxisPlan(a, cfg.strategy, cfg.factors)
        if cfg.factors and _math.prod(cfg.factors) == n:
            return AxisPlan(a, "hcps", tuple(cfg.factors))
        facs = factorizations(n)
        if facs:
            return AxisPlan(a, "hcps", tuple(facs[0]))
        return AxisPlan(a, "cps", None)

    return [axis_plan(a, n) for a, n in axes if n > 1]


# ---------------------------------------------------------------------------
# Expert-parallel AllToAll context (ISSUE 9 tentpole): the trainer opens
# `expert_parallel(...)` around loss tracing so the MoE layer's dispatch/
# combine exchanges run over the right mesh axis — and, under
# strategy="plan", from the lowered all_to_all plan instead of
# lax.all_to_all. Trace-time state, like the plan lookups themselves.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EPContext:
    axis: str                       # mesh axis the experts shard over
    size: int                       # axis size (number of expert groups)
    # lowered family="all_to_all" CompiledSchedule (possibly guarded);
    # None ⇒ lax.all_to_all
    schedule: object | None = None


_EP_CONTEXT: list = [None]


def ep_context() -> EPContext | None:
    """The active expert-parallel context, if any (trace-time)."""
    return _EP_CONTEXT[0]


class expert_parallel:
    """Context manager installing an EPContext for the enclosed trace."""

    def __init__(self, axis: str, size: int, schedule=None):
        self._ctx = EPContext(axis, int(size), schedule)
        self._prev = None

    def __enter__(self) -> EPContext:
        self._prev = _EP_CONTEXT[0]
        _EP_CONTEXT[0] = self._ctx
        return self._ctx

    def __exit__(self, *exc):
        _EP_CONTEXT[0] = self._prev
        return False


def ep_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    """AllToAll for the MoE dispatch/combine: the active EPContext's
    planned schedule when it matches `axis_name`, lax otherwise."""
    ctx = _EP_CONTEXT[0]
    sched = ctx.schedule if ctx is not None and ctx.axis == axis_name \
        else None
    return collectives.all_to_all(x, axis_name, schedule=sched)


def _quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def allreduce_int8_cps(x: jax.Array, axis_name: str) -> jax.Array:
    """CPS AllReduce with int8 wire format (gradient compression): 4× less
    β/ε cost per the paper's model, at one extra γ/δ quantize pass."""
    n = lax.psum(1, axis_name)
    shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    q, scale = _quantize_int8(flat)
    parts = lax.all_to_all(q.reshape(n, -1), axis_name,
                           split_axis=0, concat_axis=0)
    scales = lax.all_gather(scale, axis_name)           # (n,)
    shard = (parts.astype(jnp.float32) * scales[:, None]).sum(0)
    qs, sc = _quantize_int8(shard)
    full_q = lax.all_gather(qs, axis_name, axis=0, tiled=True)
    full_s = lax.all_gather(sc, axis_name)
    chunk = qs.shape[0]
    full = full_q.astype(jnp.float32) * jnp.repeat(full_s, chunk)
    if pad:
        full = full[:-pad]
    return full.reshape(shape).astype(x.dtype)


def allreduce_topk(x: jax.Array, axis_name: str, k_frac: float = 0.01
                   ) -> jax.Array:
    """Top-k sparsified AllReduce for the low-bandwidth (DCI) hop: keep
    the k·|g| largest-magnitude entries per device, exchange (values,
    indices) — wire bytes ≈ 2k vs the dense gradient. Error feedback is
    the caller's concern (runtime keeps the residual); GenModel prices the
    trade: β/ε shrink by ~1/(2·k_frac) at one extra γ/δ pass for the
    top-k selection."""
    n = lax.psum(1, axis_name)
    shape = x.shape
    flat = x.reshape(-1)
    k = max(1, int(flat.size * k_frac))
    vals, idx = lax.top_k(jnp.abs(flat), k)
    vals = flat[idx]
    # dense scatter of every device's sparse contribution: gather the
    # (vals, idx) pairs and accumulate locally — the wire cost is the
    # gathered sparse pairs, not the dense tensor.
    all_vals = lax.all_gather(vals, axis_name)      # (n, k)
    all_idx = lax.all_gather(idx, axis_name)        # (n, k)
    out = jnp.zeros_like(flat).at[all_idx.reshape(-1)].add(
        all_vals.reshape(-1))
    return out.reshape(shape)


def sync_gradients(grads, axes: Sequence[tuple[str, int]], cfg: SyncConfig,
                   fused_reduce: Callable | None = None,
                   stats: dict | None = None):
    """AllReduce every gradient leaf across the DP axes per the config.

    Must be called inside shard_map with all `axes` present. Hierarchical:
    leaf-level axis first, then outer axes — the multi-pod pattern
    (intra-pod reduce, inter-pod exchange) falls out naturally.

    `stats`, when given, is filled at trace time with the resolved
    plans' identity and modeled costs (bucketed path: the bucket plan's
    fingerprint and pipelined prediction; per-leaf path: the per-axis
    predictions) so the caller can pair them with measured timings for
    the online loop.
    """
    if cfg.strategy == "auto":
        names = tuple(a for a, n in axes if n > 1)
        return jax.tree.map(lambda g: lax.psum(g, names), grads)

    if cfg.strategy == "plan" and cfg.bucket_bytes != 0:
        # Bucketed, double-buffered execution (DESIGN.md §9): the whole
        # pytree partitions into GenModel-sized buckets and bucket k's
        # AllGather half overlaps bucket k+1's ReduceScatter half,
        # instead of one schedule launch per leaf. bucket_bytes=0 opts
        # back into the per-leaf path below.
        from .bucketing import sync_bucketed
        return sync_bucketed(grads, axes, cfg, fused_reduce=fused_reduce,
                             stats=stats)

    plans = resolve_axis_plans(axes, cfg, size_floats=float(
        sum(x.size for x in jax.tree.leaves(grads))))
    if stats is not None:
        stats.update({
            "axis_plans": [(p.axis, p.strategy, p.predicted)
                           for p in plans],
            "predicted_total": (sum(p.predicted for p in plans)
                                if all(p.predicted is not None
                                       for p in plans) and plans
                                else None),
        })

    def leaf(g):
        for pl in plans:
            if cfg.compress == "int8" and pl.strategy in ("cps", "hcps"):
                g = allreduce_int8_cps(g, pl.axis)
            else:
                g = collectives.allreduce(g, pl.axis, pl.strategy,
                                          factors=pl.factors,
                                          fused_reduce=fused_reduce,
                                          schedule=pl.schedule)
        return g

    from repro.runtime.trace import scope
    with scope("sync/gradients"):
        return jax.tree.map(leaf, grads)
