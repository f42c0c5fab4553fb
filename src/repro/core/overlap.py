"""Cross-family overlap scheduling: merged CompiledSchedules (DESIGN.md §15).

The bucket pipeline (§9) and the step co-planner (§14) both overlap a
ReduceScatter with an AllGather — RS-of-bucket-k behind AG-of-bucket-(k-1),
or the RS/AG halves of two different families from `get_step_plan`. Until
now that overlap was an *issuance order* (two schedule launches back to
back, XLA free to reorder); this module turns it into one **merged
schedule**: the two constituents' ppermute rounds interleave round-by-round
over their own independent buffers, so the overlap the planner priced with
the contended model (`cost_model.contended_pair_time` /
`FastEngine.contended_pair_total`) is the overlap that is actually issued.

Key facts the merge leans on:

* The two constituents operate on DISJOINT buffers, so any interleaving
  that preserves each schedule's internal round/fold order is numerically
  identical to sequential execution (tests/test_overlap.py proves this by
  differential + hypothesis sweeps over interleavings).
* A round pair is **coalesced** (issued adjacently, modeled as fully
  overlapped) exactly when its link sets are disjoint. On a single-switch
  axis a device's NIC is its up/down link pair, so link-disjointness of
  two rounds is: no device sends in both AND no device receives in both —
  the same partial-permutation invariant `lower._color_rounds` enforces
  within one round. Shared-link pairs still execute correctly (separate
  ppermutes) but the contended price charges their serialized β/ε.
* Dataflow validity comes from `core.lower`: the constituents were
  validated by `lower_plan`, execution reuses `lower._round_jax` /
  `lower._fold_jax`, and `plan_merge` re-checks the merge-specific
  contract (same axis size, canonical shards, compatible families).

Guard ladder: a `MergedSchedule` is itself a guard rung. A fault (or an
armed `runtime.faults` injector) during the merged launch demotes it —
sticky, registered with `lower._GUARD_REGISTRY` so `reprobe_guards`
re-arms it — and the launch falls back to SEQUENTIAL execution through the
constituents' own `GuardedSchedule` ladders, so compression and faults
keep demoting exactly as before (merged → sequential → per-constituent
compressed → full-precision → flat lax collective).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence

import numpy as np

from repro.runtime.metrics import default_metrics
from repro.runtime.trace import default_tracer, scope

from .lower import (ExecStep, LoweringError, PermRound, _fold_jax,
                    _round_jax, _GUARD_REGISTRY, guard_schedule)


# ---------------------------------------------------------------------------
# Merge analysis
# ---------------------------------------------------------------------------
def _round_endpoints(rd: PermRound) -> tuple[set, set]:
    senders = {s for s, _ in rd.perm}
    receivers = {d for _, d in rd.perm}
    return senders, receivers


def rounds_link_disjoint(ra: PermRound, rb: PermRound) -> bool:
    """True when the two rounds occupy disjoint link sets on a
    single-switch axis: a device's up-link carries its send, its
    down-link its receive, so disjointness is 'no common sender and no
    common receiver'. Disjoint pairs coalesce (fully overlap, priced at
    max); shared pairs serialize their β/ε in the contended model."""
    sa, ra_ = _round_endpoints(ra)
    sb, rb_ = _round_endpoints(rb)
    return not (sa & sb) and not (ra_ & rb_)


def _unwrap(sched):
    """The raw CompiledSchedule under a (possibly) guarded schedule."""
    return getattr(sched, "inner", sched)


def _rs_steps(sched) -> list[ExecStep]:
    """The step stream the RS constituent contributes: its RS half plus
    the canonical reorder round."""
    return list(sched.rs) + ([sched.reorder]
                             if sched.reorder is not None else [])


def _ag_steps(sched) -> list[ExecStep]:
    """The step stream the AG constituent contributes: the unorder round
    plus its AG half."""
    return ([sched.unorder] if sched.unorder is not None else []) \
        + list(sched.ag)


@dataclass(frozen=True)
class MergeInfo:
    """Static analysis of one merge: how many round pairs interleave and
    how many coalesce (disjoint link sets). `coalesced_fraction` is what
    the trace span and the occupancy gauge report — a low fraction means
    the contended price sits near serial and the planner should usually
    reject the merge."""
    n: int
    steps_rs: int
    steps_ag: int
    round_pairs: int
    coalesced: int

    @property
    def serialized(self) -> int:
        return self.round_pairs - self.coalesced

    @property
    def coalesced_fraction(self) -> float:
        return self.coalesced / self.round_pairs if self.round_pairs else 1.0


def plan_merge(rs_sched, ag_sched) -> MergeInfo:
    """Validate that `rs_sched`'s RS half can merge with `ag_sched`'s AG
    half and analyze the interleaving. Raises LoweringError on any
    contract violation (the dataflow contract of core.lower carries
    over: both constituents were validated by `lower_plan`; the merge
    only adds cross-schedule requirements)."""
    a, b = _unwrap(rs_sched), _unwrap(ag_sched)
    if a.n != b.n:
        raise LoweringError(
            f"cannot merge schedules over different axis sizes: "
            f"{a.plan_name!r} has n={a.n}, {b.plan_name!r} n={b.n}")
    if a.family not in ("allreduce", "reduce_scatter"):
        raise LoweringError(
            f"merge RS side must be allreduce/reduce_scatter family; "
            f"{a.plan_name!r} is {a.family!r}")
    if b.family not in ("allreduce", "allgather"):
        raise LoweringError(
            f"merge AG side must be allreduce/allgather family; "
            f"{b.plan_name!r} is {b.family!r}")
    for s, what in ((a, "RS"), (b, "AG")):
        if s.blocks_per_shard is None:
            raise LoweringError(
                f"merge {what} side {s.plan_name!r} has no canonical "
                f"shard layout (num_blocks % n != 0)")
    sa, sb = _rs_steps(a), _ag_steps(b)
    pairs = coalesced = 0
    for i in range(min(len(sa), len(sb))):
        ra, rb = sa[i].rounds, sb[i].rounds
        for j in range(min(len(ra), len(rb))):
            pairs += 1
            if rounds_link_disjoint(ra[j], rb[j]):
                coalesced += 1
    return MergeInfo(n=a.n, steps_rs=len(sa), steps_ag=len(sb),
                     round_pairs=pairs, coalesced=coalesced)


# ---------------------------------------------------------------------------
# Merged schedule
# ---------------------------------------------------------------------------
class MergedSchedule:
    """One RS half and one AG half interleaved into a single issuance.

    `rs_ag(x, shard, axis)` runs `rs_sched.reduce_scatter(x)` and
    `ag_sched.all_gather(shard)` as ONE interleaved round stream and
    returns `(rs_shard, ag_full)`. Both constituents may be guarded
    and/or wire-bound; the merged path interleaves at round granularity
    when both run full precision, and at step granularity otherwise
    (each step then runs through the constituent's own compressed
    `_run_steps_wire`, so quantized payloads and scale plumbing are
    untouched).

    Guard contract (duck-typed against `GuardedSchedule` so
    `reprobe_guards` re-arms it): a failed merged launch demotes the
    wrapper — subsequent launches run the constituents SEQUENTIALLY
    through their own guard ladders, preserving every lower rung.
    """

    def __init__(self, rs_sched, ag_sched, *, telemetry=None, policy=None):
        self.info = plan_merge(rs_sched, ag_sched)
        # keep the guarded wrappers as the sequential fallback rung; the
        # raw schedules drive the merged path
        self.rs_guard = guard_schedule(rs_sched, telemetry=telemetry,
                                       policy=policy)
        self.ag_guard = guard_schedule(ag_sched, telemetry=telemetry,
                                       policy=policy)
        self.rs_inner = _unwrap(rs_sched)
        self.ag_inner = _unwrap(ag_sched)
        self.telemetry = telemetry
        self.plan_name = (f"merge({self.rs_inner.plan_name}"
                          f"+{self.ag_inner.plan_name})")
        self.n = self.rs_inner.n
        self._demoted = False
        self._wire_demoted = False      # reprobe_guards duck-type
        self.stats = {"launches": 0, "fallbacks": 0,
                      "demoted_launches": 0, "reprobes": 0}
        _GUARD_REGISTRY.add(self)

    # -- guard duck-type ----------------------------------------------------
    @property
    def demoted(self) -> bool:
        return self._demoted

    def reset_guard(self) -> None:
        self._demoted = False
        self._wire_demoted = False

    def describe(self) -> str:
        i = self.info
        return (f"{self.plan_name}: n={self.n} steps={i.steps_rs}"
                f"|{i.steps_ag} round_pairs={i.round_pairs} "
                f"coalesced={i.coalesced} "
                f"({i.coalesced_fraction:.0%} disjoint)")

    def _remeasure(self, reason: str, info: dict) -> None:
        tele = self.telemetry
        if tele is None:
            from repro.runtime.telemetry import peek_default_telemetry
            tele = peek_default_telemetry()
        if tele is not None:
            tele.remeasure(reason, info)

    # -- sequential fallback rung -------------------------------------------
    def _sequential(self, x, shard, axis_name: str,
                    fused_reduce: Callable | None):
        new_shard = self.rs_guard.reduce_scatter(
            x, axis_name, fused_reduce=fused_reduce)
        full = self.ag_guard.all_gather(shard, axis_name)
        return new_shard, full

    # -- merged execution ----------------------------------------------------
    def _merged(self, x, shard, axis_name: str,
                fused_reduce: Callable | None):
        import jax.numpy as jnp
        from jax import lax

        a, b = self.rs_inner, self.ag_inner
        idx = lax.axis_index(axis_name)

        # RS-side buffer prep (mirrors CompiledSchedule.reduce_scatter)
        flat = x.reshape(-1)
        pad_a = (-flat.size) % a.num_blocks
        if pad_a:
            flat = jnp.concatenate([flat, jnp.zeros((pad_a,), flat.dtype)])
        buf_a = flat.reshape(a.num_blocks, -1)

        # AG-side buffer prep (mirrors CompiledSchedule.all_gather)
        kb = b.blocks_per_shard
        sflat = shard.reshape(-1)
        buf_b = jnp.zeros((b.num_blocks, sflat.size // kb), sflat.dtype)
        buf_b = lax.dynamic_update_slice_in_dim(
            buf_b, sflat.reshape(kb, -1), idx * kb, axis=0)

        steps_a, steps_b = _rs_steps(a), _ag_steps(b)
        # the reorder step (last of steps_a) runs foldless-movement
        # semantics: fused_reduce never applies there in the sequential
        # entry points, so mirror that boundary exactly
        n_rs = len(a.rs)
        with scope("overlap/rs_ag"):
            if a.wire is None and b.wire is None:
                chunk_a, chunk_b = buf_a.shape[1], buf_b.shape[1]
                zero_a = jnp.zeros((chunk_a,), buf_a.dtype)
                zero_b = jnp.zeros((chunk_b,), buf_b.dtype)
                for i in range(max(len(steps_a), len(steps_b))):
                    sa = steps_a[i] if i < len(steps_a) else None
                    sb = steps_b[i] if i < len(steps_b) else None
                    with scope(f"overlap/step{i}"):
                        stage_a = jnp.zeros(
                            (max(sa.n_slots, 1), chunk_a),
                            buf_a.dtype) if sa is not None else None
                        stage_b = jnp.zeros(
                            (max(sb.n_slots, 1), chunk_b),
                            buf_b.dtype) if sb is not None else None
                        ra = sa.rounds if sa is not None else []
                        rb = sb.rounds if sb is not None else []
                        for j in range(max(len(ra), len(rb))):
                            if j < len(ra):
                                stage_a = _round_jax(
                                    ra[j], buf_a, stage_a, idx, zero_a,
                                    axis_name, j)
                            if j < len(rb):
                                stage_b = _round_jax(
                                    rb[j], buf_b, stage_b, idx, zero_b,
                                    axis_name, j)
                        if sa is not None:
                            fr = fused_reduce if i < n_rs else None
                            for fi, fd in enumerate(sa.folds):
                                buf_a = _fold_jax(fd, buf_a, stage_a,
                                                  idx, zero_a, fr, fi)
                        if sb is not None:
                            for fi, fd in enumerate(sb.folds):
                                buf_b = _fold_jax(fd, buf_b, stage_b,
                                                  idx, zero_b, None, fi)
            else:
                # compressed constituent(s): interleave at step
                # granularity — each step keeps its own wire machinery
                for i in range(max(len(steps_a), len(steps_b))):
                    if i < len(steps_a):
                        fr = fused_reduce if i < n_rs else None
                        buf_a = a._run_steps([steps_a[i]], buf_a,
                                             axis_name, fr, phase="rs")
                    if i < len(steps_b):
                        buf_b = b._run_steps([steps_b[i]], buf_b,
                                             axis_name, None, phase="ag")

        ka = a.blocks_per_shard
        new_shard = lax.dynamic_slice_in_dim(
            buf_a, idx * ka, ka, axis=0).reshape(-1)
        return new_shard, buf_b.reshape(-1)

    def rs_ag(self, x, shard, axis_name: str, *,
              fused_reduce: Callable | None = None):
        """Merged launch: RS of `x` interleaved with AG of `shard`.
        Returns `(rs_shard, ag_full)` — identical values to running the
        constituents sequentially."""
        m = default_metrics()
        self.stats["launches"] += 1
        m.counter("overlap_merged_launches_total",
                  "merged RS+AG launches through the overlap scheduler"
                  ).inc()
        if self._demoted:
            self.stats["demoted_launches"] += 1
            m.counter("overlap_merged_demoted_launches_total",
                      "merged launches served sequentially after demotion"
                      ).inc()
            return self._sequential(x, shard, axis_name, fused_reduce)
        try:
            from repro.runtime.faults import active_injector
            inj = active_injector()
            if inj is not None:
                inj.check_launch(f"{self.plan_name}/rs_ag")
            return self._merged(x, shard, axis_name, fused_reduce)
        except Exception as e:            # noqa: BLE001 — ladder rung
            self.stats["fallbacks"] += 1
            self._demoted = True
            m.counter("overlap_merged_fallbacks_total",
                      "merged launches demoted to sequential execution"
                      ).inc()
            default_tracer().instant("overlap/fallback",
                                     plan=self.plan_name, error=repr(e))
            self._remeasure("overlap_fallback",
                            {"plan": self.plan_name, "error": repr(e)})
            return self._sequential(x, shard, axis_name, fused_reduce)

    # -- numpy mirror (reference; tests) -------------------------------------
    def run_numpy_pair(self, X: np.ndarray, shards: np.ndarray,
                       order: Sequence[str] | None = None
                       ) -> tuple[np.ndarray, np.ndarray]:
        """Numpy mirror of `rs_ag` with a controllable interleaving.

        `X` is the (n, size) per-device RS contribution matrix; `shards`
        the (n, shard_size) per-device AG input shards. `order` is a
        token stream over {'a', 'b'} consumed step-wise (default: strict
        alternation, the merged executor's order) — any order preserving
        each constituent's internal sequence must produce identical
        results, which is exactly what the hypothesis sweep asserts.
        Returns `(rs_shards (n, k·chunk), ag_full (n, num_blocks·chunk))`
        at full precision (like `CompiledSchedule.run_numpy`)."""
        a, b = self.rs_inner, self.ag_inner
        n = self.n
        X = np.asarray(X)
        if X.shape[0] != n or np.asarray(shards).shape[0] != n:
            raise LoweringError(f"expected {n} device rows")
        size = X.shape[1]
        pad_a = (-size) % a.num_blocks
        if pad_a:
            X = np.concatenate([X, np.zeros((n, pad_a), X.dtype)], axis=1)
        buf_a = X.reshape(n, a.num_blocks, -1).copy()

        shards = np.asarray(shards)
        kb = b.blocks_per_shard
        chunk_b = shards.shape[1] // kb
        buf_b = np.zeros((n, b.num_blocks, chunk_b), shards.dtype)
        for d in range(n):
            buf_b[d, d * kb:(d + 1) * kb] = shards[d].reshape(kb, -1)

        steps_a, steps_b = _rs_steps(a), _ag_steps(b)
        if order is None:
            order = []
            for i in range(max(len(steps_a), len(steps_b))):
                if i < len(steps_a):
                    order.append("a")
                if i < len(steps_b):
                    order.append("b")
        toks = list(order)
        if (toks.count("a") != len(steps_a)
                or toks.count("b") != len(steps_b)
                or len(toks) != len(steps_a) + len(steps_b)):
            raise LoweringError(
                f"interleaving order needs exactly {len(steps_a)} 'a' and "
                f"{len(steps_b)} 'b' tokens, got {toks!r}")
        ia = ib = 0
        for tok in toks:
            if tok == "a":
                buf_a = a._run_steps_numpy([steps_a[ia]], buf_a,
                                           phase="rs")
                ia += 1
            else:
                buf_b = b._run_steps_numpy([steps_b[ib]], buf_b,
                                           phase="ag")
                ib += 1

        ka = a.blocks_per_shard
        rs_out = np.stack([buf_a[d, d * ka:(d + 1) * ka].reshape(-1)
                           for d in range(n)])
        return rs_out, buf_b.reshape(n, -1)


def merge_schedules(rs_sched, ag_sched, *, telemetry=None,
                    policy=None) -> MergedSchedule:
    """Build (and validate) a MergedSchedule. Memoized per (rs, ag)
    schedule-object pair on the RS schedule, mirroring `guard_schedule`'s
    per-object memo, so demotion state survives re-resolves of the same
    cached schedules."""
    inner = _unwrap(rs_sched)
    memo = getattr(inner, "_merge_wrappers", None)
    if memo is None:
        memo = {}
        try:
            inner._merge_wrappers = memo
        except (AttributeError, TypeError):
            return MergedSchedule(rs_sched, ag_sched, telemetry=telemetry,
                                  policy=policy)
    key = id(_unwrap(ag_sched))
    ms = memo.get(key)
    if ms is None:
        ms = MergedSchedule(rs_sched, ag_sched, telemetry=telemetry,
                            policy=policy)
        memo[key] = ms
    return ms


# ---------------------------------------------------------------------------
# Occupancy summary (satellite of DESIGN.md §15: the gauge + span
# attributes that make Chrome traces show which links serialized)
# ---------------------------------------------------------------------------
def occupancy_summary(topo, step_a, step_b, unit_bytes: int = 4) -> dict:
    """Merged per-link occupancy of two concurrent Steps: how many links
    each side touches, how many they share, and the busiest link's
    combined units — the quantities the planner emits as the
    `overlap_*` gauges and `overlap/priced` span attributes."""
    from .cost_model import link_occupancy
    oa = link_occupancy(topo, step_a, unit_bytes)
    ob = link_occupancy(topo, step_b, unit_bytes)
    shared = set(oa.link_units) & set(ob.link_units)
    merged = oa.merge(ob)
    busiest, units = -1, 0.0
    for lid, u in merged.link_units.items():
        if u > units:
            busiest, units = int(lid), float(u)
    return {"links_rs": len(oa.link_units), "links_ag": len(ob.link_units),
            "links_shared": len(shared), "busiest_link": busiest,
            "busiest_link_units": units}
