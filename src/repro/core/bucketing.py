"""Gradient bucketing + double-buffered pipelined plan execution (DESIGN.md §9).

GenModel's two new terms pull the gradient bucket size in opposite
directions: the memory-access term (γ/δ) and the per-round launch term (α)
penalize many small fragmented reduces, while the incast (ε) and
serialization terms penalize one monolithic transfer whose rounds cannot
overlap. The paper's own cost model therefore *picks* the bucket size:
`PlannerService.get_bucket_plan` sweeps powers-of-two candidates, prices
each with GenModel (FastEngine by default), and returns the argmin together
with one lowered `CompiledSchedule` per axis (cached on the plan entry —
never re-lowered per step).

This module holds the mechanics around that decision:

  * `partition(sizes, dtypes, cap[, itemsizes])` — split the flattened
    gradient pytree into size-bounded (byte-bounded with itemsizes),
    dtype-homogeneous `Bucket`s (empty leaves pass through, an
    oversized leaf rides alone);
  * `pipelined_time` / `serial_time` — the two-stage pipeline model the
    sweep prices: with K buckets, bucket k's AllGather half overlaps
    bucket k+1's ReduceScatter half, so
    T = T_RS + (K−1)·max(T_RS, T_AG) + T_AG instead of K·(T_RS + T_AG);
  * `execute_buckets` — the double-buffered executor: per bucket an RS
    chain over the DP axes (leaf axis first) then the mirrored AG chain,
    issued so that bucket k+1's RS is in flight before bucket k's AG
    drains (XLA may overlap the independent collectives; the issuance
    order documents the modeled schedule). Falls back to sequential
    per-bucket `allreduce` when a schedule has no canonical RS/AG halves;
  * `sync_bucketed` — the `SyncConfig(strategy="plan")` entry point used
    by `core.sync.sync_gradients`;
  * `zero3_gather_bucketed` / `zero3_scatter_bucketed` — the ZeRO-3
    trainer's bucketed param-AllGather / grad-ReduceScatter (one schedule
    launch per bucket instead of per leaf; single-DP-axis layout);
  * `invalidate_schedules` — drops every lowered schedule and bucket plan
    derived from a service's cache. Called after `elastic_remesh` and on
    `FaultTolerantLoop` resume: a schedule compiled for the old axis size
    must not survive an axis-size change.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Sequence

from repro.runtime.metrics import default_metrics
from repro.runtime.trace import scope


# ---------------------------------------------------------------------------
# Config + bucket structure
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class BucketConfig:
    """How gradients are bucketed for plan execution.

    bucket_bytes: None → "auto" (GenModel argmin over the sweep);
    an explicit int fixes the bucket size; 0 disables bucketing entirely
    (legacy per-leaf execution).

    precision pins a wire format by name ("f32"/"bf16"/"fp8"/"int8");
    None lets the sweep argmin over every format `tolerance` allows.
    tolerance is the caller's per-sync relative error budget: None means
    no lossy consent (the sweep stays lossless; a pinned lossy precision
    is trusted as explicit opt-in), a float clamps any format whose
    `Precision.error_budget` exceeds it to full precision
    (DESIGN.md §13). Both are part of `key()` — and therefore of the
    bucket-plan cache fingerprint — so a tolerance change can never be
    served a stale compressed schedule.
    """
    bucket_bytes: int | None = None
    pipeline: bool = True               # overlap AG(k) with RS(k+1)
    min_bucket_bytes: int = 1 << 18     # sweep floor (256 KiB)
    max_bucket_bytes: int = 1 << 28     # sweep ceiling (256 MiB)
    precision: str | None = None        # pinned wire format (None: sweep)
    tolerance: float | None = None      # error budget (None: lossless only)

    def __post_init__(self):
        if self.bucket_bytes is not None and self.bucket_bytes < 0:
            raise ValueError(
                f"bucket_bytes must be None (auto), 0 (off) or positive; "
                f"got {self.bucket_bytes}")
        if not 0 < self.min_bucket_bytes <= self.max_bucket_bytes:
            raise ValueError(
                f"need 0 < min_bucket_bytes <= max_bucket_bytes; got "
                f"{self.min_bucket_bytes}..{self.max_bucket_bytes}")
        if self.precision is not None:
            from .cost_model import PRECISIONS
            if self.precision not in PRECISIONS:
                raise ValueError(
                    f"unknown precision {self.precision!r}; one of "
                    f"{sorted(PRECISIONS)}")

    @property
    def enabled(self) -> bool:
        return self.bucket_bytes != 0

    def key(self) -> tuple:
        return (self.bucket_bytes if self.bucket_bytes is not None else -1,
                int(self.pipeline), self.min_bucket_bytes,
                self.max_bucket_bytes, self.precision or "",
                -1.0 if self.tolerance is None else float(self.tolerance))


@dataclass(frozen=True)
class Bucket:
    """One dtype-homogeneous group of leaf positions, bounded in size."""
    indices: tuple[int, ...]            # leaf positions (flattened order)
    sizes: tuple[int, ...]              # element count per member leaf
    dtype: object                       # shared numpy/jax dtype

    @property
    def size(self) -> int:
        return sum(self.sizes)


def partition(sizes: Sequence[int], dtypes: Sequence[object],
              cap: int | float,
              itemsizes: Sequence[int] | None = None) -> list[Bucket]:
    """Greedy, order-preserving partition of the flattened leaf list into
    dtype-homogeneous buckets. `cap` bounds each bucket's total *elements*
    — or total *bytes* when `itemsizes` (per-leaf element widths) is
    given, so a mixed f32/bf16 pytree honours one byte budget across both
    dtype classes instead of letting the wider dtype carry itemsize× the
    bound.

    Leaves keep their relative order within each dtype class; a leaf larger
    than the bound gets a bucket of its own; empty (size-0) leaves are
    assigned to no bucket (the executor passes them through unchanged).
    Buckets are returned ordered by their first member's leaf index, so the
    output is deterministic for a given leaf list.
    """
    cap = max(1, int(cap))
    weights = [int(s) for s in sizes] if itemsizes is None else \
        [int(s) * int(w) for s, w in zip(sizes, itemsizes)]
    open_by_dtype: dict[object, list[tuple[int, int]]] = {}
    open_weight: dict[object, int] = {}
    closed: list[list[tuple[int, int]]] = []

    def close(key):
        members = open_by_dtype.pop(key, None)
        open_weight.pop(key, None)
        if members:
            closed.append(members)

    for i, (sz, dt) in enumerate(zip(sizes, dtypes)):
        sz = int(sz)
        if sz == 0:
            continue
        key = str(dt)
        cur = open_by_dtype.setdefault(key, [])
        if cur and open_weight.get(key, 0) + weights[i] > cap:
            close(key)
            cur = open_by_dtype.setdefault(key, [])
        cur.append((i, sz))
        open_weight[key] = open_weight.get(key, 0) + weights[i]
        if weights[i] >= cap:
            close(key)
    for key in list(open_by_dtype):
        close(key)

    closed.sort(key=lambda members: members[0][0])
    return [Bucket(indices=tuple(i for i, _ in members),
                   sizes=tuple(s for _, s in members),
                   dtype=dtypes[members[0][0]])
            for members in closed]


# ---------------------------------------------------------------------------
# Pipeline time model (what the sweep prices)
# ---------------------------------------------------------------------------
def serial_time(t_rs: float, t_ag: float, k: int) -> float:
    """K buckets executed back-to-back: no overlap."""
    return max(0, k) * (t_rs + t_ag)


def pipelined_time(t_rs: float, t_ag: float, k: int) -> float:
    """Two-stage software pipeline: bucket k's AG overlaps bucket k+1's RS,
    so the steady state advances one bucket per max(T_RS, T_AG).

    This is the NAIVE model — it assumes the overlapped halves never share
    a link. Kept as the optimistic baseline the contended model is
    benchmarked against (`contended_vs_naive_pipeline_error`); the sweep
    itself ranks on `contended_pipelined_time`."""
    if k <= 0:
        return 0.0
    if k == 1:
        return t_rs + t_ag
    return t_rs + (k - 1) * max(t_rs, t_ag) + t_ag


def contended_pipelined_time(t_rs: float, t_ag: float, k: int,
                             t_joint: float | None = None) -> float:
    """Link-contention-aware pipeline model (DESIGN.md §15): the steady
    state advances one bucket per the CONTENDED concurrent time of the
    RS and AG halves — `t_joint`, priced by merging the halves' per-link
    occupancy vectors (`FastEngine.contended_pair_total` /
    `cost_model.contended_pair_time`) — not their optimistic `max()`.

    On disjoint links t_joint == max(t_rs, t_ag) and this reduces to
    `pipelined_time`; on shared links the serialized β/ε push it toward
    (and past — summed incast fan-in crossing w_t) t_rs + t_ag. The
    planner controls issuance, so the steady state never does worse than
    back-to-back halves: t_joint clamps to [max(t_rs, t_ag), t_rs + t_ag].
    """
    if k <= 0:
        return 0.0
    if k == 1:
        return t_rs + t_ag
    if t_joint is None:
        t_joint = max(t_rs, t_ag)
    t_joint = min(max(t_joint, max(t_rs, t_ag)), t_rs + t_ag)
    return t_rs + (k - 1) * t_joint + t_ag


# ---------------------------------------------------------------------------
# Executors (call inside shard_map; all shapes static at trace time)
# ---------------------------------------------------------------------------
def supports_halves(axis_plans) -> bool:
    """True when every axis schedule exposes the canonical RS/AG halves
    the double-buffered pipeline needs; otherwise execute_buckets
    degrades to sequential whole-plan allreduce per bucket."""
    return all(pl.schedule is not None
               and getattr(pl.schedule, "blocks_per_shard", None)
               for pl in axis_plans)


def _rs_chain(vec, axis_plans, fused_reduce):
    """Hierarchical ReduceScatter: leaf axis first. Returns the final shard
    plus the pre-RS vector size per axis (needed to undo schedule padding
    on the mirrored AG chain)."""
    sizes = []
    for pl in axis_plans:
        sizes.append(vec.size)
        vec = pl.schedule.reduce_scatter(vec, pl.axis,
                                         fused_reduce=fused_reduce)
    return vec, sizes


def _ag_chain(shard, axis_plans, sizes):
    for pl, sz in zip(reversed(axis_plans), reversed(sizes)):
        shard = pl.schedule.all_gather(shard, pl.axis)[:sz]
    return shard


def _allreduce_chain(vec, axis_plans, fused_reduce):
    for pl in axis_plans:
        vec = pl.schedule.allreduce(vec, pl.axis, fused_reduce=fused_reduce)
    return vec


def execute_buckets(leaves, buckets: Sequence[Bucket], axis_plans, *,
                    pipeline: bool = True,
                    fused_reduce: Callable | None = None,
                    merged=None, reverse: bool = False) -> list:
    """AllReduce every bucket across the DP axes; returns the reduced
    leaf list (leaves outside any bucket — empty leaves — unchanged).

    Scheduler state machine (DESIGN.md §9): each bucket moves
    QUEUED → RS → SHARD → AG → DONE with at most two buckets in flight;
    at step k the executor issues RS(bucket k) *then* AG(bucket k−1), so
    the next bucket's reduce is on the wire before the previous bucket's
    gather drains.

    `reverse=True` issues buckets in reverse-layer readiness order
    (DESIGN.md §15): backward produces gradients last-layer-first, and
    the greedy partition orders buckets by first leaf index, so the
    LAST bucket's gradients materialize first — issuing k−1, k−2, … lets
    each RS leave as soon as its bucket is ready instead of stalling on
    bucket 0. Results land in leaf order either way.

    `merged` (a `core.overlap.MergedSchedule` from the bucket plan's
    {sequential, merged} argmin) fuses each steady-state step into ONE
    round-interleaved launch — RS(bucket k) coalesced with AG(bucket
    k−1) on their disjoint links. Single-axis chains only (the
    hierarchical handoff already serializes at the axis boundary);
    ignored otherwise.
    """
    import jax.numpy as jnp

    out = list(leaves)
    if not buckets:
        return out
    flats = []
    for bk in buckets:
        parts = [leaves[i].reshape(-1) for i in bk.indices]
        flats.append(parts[0] if len(parts) == 1
                     else jnp.concatenate(parts))

    k = len(flats)
    order = list(range(k - 1, -1, -1)) if reverse else list(range(k))
    results: list = [None] * k
    use_merged = (merged is not None and pipeline and k > 1
                  and len(axis_plans) == 1 and supports_halves(axis_plans))
    if use_merged:
        pl = axis_plans[0]
        shards: list = [None] * k
        prev = None
        for i in order:
            if prev is None:
                with scope(f"bucket/rs{i}"):
                    shards[i] = pl.schedule.reduce_scatter(
                        flats[i], pl.axis, fused_reduce=fused_reduce)
            else:
                with scope(f"bucket/rs_ag{i}"):
                    shards[i], full = merged.rs_ag(
                        flats[i], shards[prev], pl.axis,
                        fused_reduce=fused_reduce)
                results[prev] = full[:int(flats[prev].size)]
                shards[prev] = None
            prev = i
        with scope(f"bucket/ag{prev}"):
            results[prev] = pl.schedule.all_gather(
                shards[prev], pl.axis)[:int(flats[prev].size)]
    elif pipeline and k > 1 and supports_halves(axis_plans):
        shards, sizes = [None] * k, [None] * k
        prev = None
        for i in order:
            with scope(f"bucket/rs{i}"):
                shards[i], sizes[i] = _rs_chain(flats[i], axis_plans,
                                                fused_reduce)
            if prev is not None:
                with scope(f"bucket/ag{prev}"):
                    results[prev] = _ag_chain(shards[prev], axis_plans,
                                              sizes[prev])
                shards[prev] = None
            prev = i
        with scope(f"bucket/ag{prev}"):
            results[prev] = _ag_chain(shards[prev], axis_plans,
                                      sizes[prev])
    elif supports_halves(axis_plans):
        for i in order:
            with scope(f"bucket/rs{i}"):
                shard, sizes = _rs_chain(flats[i], axis_plans,
                                         fused_reduce)
            with scope(f"bucket/ag{i}"):
                results[i] = _ag_chain(shard, axis_plans, sizes)
    else:
        # no canonical shard layout on some axis: sequential whole-plan
        # AllReduce per bucket (still amortizes per-leaf launches)
        for i in order:
            with scope(f"bucket/allreduce{i}"):
                results[i] = _allreduce_chain(flats[i], axis_plans,
                                              fused_reduce)

    for bk, res in zip(buckets, results):
        off = 0
        for i, sz in zip(bk.indices, bk.sizes):
            out[i] = res[off:off + sz].reshape(leaves[i].shape)
            off += sz
    return out


def sync_bucketed(grads, axes: Sequence[tuple[str, int]], cfg, *,
                  service=None, fused_reduce: Callable | None = None,
                  stats: dict | None = None):
    """Bucketed, double-buffered gradient AllReduce — the
    `SyncConfig(strategy="plan")` execution path of
    `core.sync.sync_gradients`. Must run inside shard_map with every
    axis present. The bucket size, per-axis plans and their lowered
    schedules come from `PlannerService.get_bucket_plan` (resolved at
    trace time; warm lookups are a cache probe).

    `stats`, when given, is filled in place with the resolved bucket
    plan's identity and modeled costs (plan fingerprint key, bucket
    size, bucket count, predicted pipelined/serial seconds) — the
    trainer pairs these predictions with measured step timings when it
    feeds the online loop (`PlannerService.observe`, DESIGN.md §10)."""
    import jax

    leaves, treedef = jax.tree.flatten(grads)
    sizes = [int(x.size) for x in leaves]
    total = float(sum(sizes))
    live = [(a, int(n)) for a, n in axes if int(n) > 1]
    if not live or total == 0 or not leaves:
        return grads

    if service is None:
        from repro.planner.service import default_service
        service = default_service()
    bcfg = BucketConfig(bucket_bytes=cfg.bucket_bytes,
                        pipeline=cfg.pipeline,
                        precision=getattr(cfg, "precision", None),
                        tolerance=getattr(cfg, "tolerance", None))
    # price in f32-equivalent units of the tree's total BYTES, so the
    # chosen byte budget does not depend on which dtype happens to
    # flatten first in a mixed-dtype pytree
    total_bytes = sum(int(x.size) * x.dtype.itemsize for x in leaves)
    bplan = service.get_bucket_plan(axes, total_bytes / 4.0,
                                    dtype="float32",
                                    params=cfg.params, config=bcfg)
    # backward-overlapped issuance (DESIGN.md §15): reverse-layer order
    # plus the merged RS/AG launch, but ONLY when the planner's
    # {sequential, merged} argmin says the contended price wins
    reverse = bool(getattr(cfg, "backward_overlap", True))
    merged = bplan.merged_schedule \
        if bplan.overlap.get("mode") == "merged" else None
    if stats is not None:
        stats.update({
            "key": bplan.key, "source": bplan.source,
            "axes": list(bplan.axes),
            "bucket_floats": bplan.bucket_floats,
            "bucket_bytes": bplan.bucket_bytes,
            "num_buckets": bplan.num_buckets,
            "precision": bplan.precision,
            "predicted_pipelined": bplan.predicted_pipelined,
            "predicted_serial": bplan.predicted_serial,
            "predicted_contended": bplan.predicted_contended,
            "overlap_mode": bplan.overlap.get("mode", "sequential"),
            "backward_overlap": reverse,
        })
    # byte-capped partition: every dtype class honours the same budget
    buckets = partition(sizes, [x.dtype for x in leaves],
                        bplan.bucket_bytes,
                        itemsizes=[x.dtype.itemsize for x in leaves])
    m = default_metrics()
    m.counter("sync_bucketed_total",
              "bucketed plan-strategy gradient syncs").inc()
    m.histogram("sync_buckets_per_step",
                "buckets per sync_bucketed call",
                buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0)
                ).observe(float(len(buckets)))
    # pipeline occupancy: modeled speedup of the double-buffered
    # schedule over serial execution, normalized to [0.5, 1] — 0.5 for
    # a single bucket (nothing overlaps), → 1 as the RS/AG halves
    # balance and the bucket count grows (DESIGN.md §9's pipeline model).
    # Charged on the CONTENDED pipeline estimate (§15), so the gauge
    # reflects what link sharing leaves of the modeled overlap.
    contended = bplan.predicted_contended or bplan.predicted_pipelined
    if contended > 0.0:
        m.gauge("bucket_pipeline_occupancy",
                "modeled serial/contended speedup, normalized to [.5,1]"
                ).set(bplan.predicted_serial / (2.0 * contended))
    if merged is not None:
        m.counter("sync_bucketed_merged_issue_total",
                  "syncs issued with the merged RS/AG schedule "
                  "(planner argmin chose merged)").inc()
    axis_plans = bplan.axis_plans
    if getattr(cfg, "guard", True):
        # guard the executed schedules (DESIGN.md §12); guard_schedule
        # memoizes per underlying schedule, so demotion state persists
        # across steps that cache-hit the same bucket plan
        import dataclasses as _dc

        from .lower import guard_schedule
        tele = getattr(service, "telemetry", None)
        axis_plans = [
            _dc.replace(pl, schedule=guard_schedule(pl.schedule,
                                                    telemetry=tele))
            if pl.schedule is not None else pl
            for pl in axis_plans]
    with scope("sync/bucketed"):
        out = execute_buckets(leaves, buckets, axis_plans,
                              pipeline=bcfg.pipeline,
                              fused_reduce=fused_reduce,
                              merged=merged, reverse=reverse)
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# ZeRO-3 bucketed halves (single DP axis; launch/train.py manual engine)
# ---------------------------------------------------------------------------
def _pad_to(vec, multiple: int):
    import jax.numpy as jnp
    pad = (-vec.size) % multiple
    if pad:
        vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
    return vec


def zero3_gather_bucketed(shards, specs, plan, bucket_bytes: int, n: int
                          ) -> list:
    """Bucketed parameter AllGather for the ZeRO-3 row layout.

    `shards[ℓ]` is leaf ℓ's flat per-device shard (row i of the leaf
    padded to a multiple of `n` and reshaped (n, chunk_ℓ) — the
    `shard_params_zero3` layout); `specs[ℓ] = (shape, dtype)` describes
    the full leaf. Same-dtype shards concatenate into one row per bucket,
    padded to the schedule's blocks-per-shard multiple, and ONE
    `all_gather` launch per bucket reassembles the n rows end to end;
    each leaf is the concatenation of its n contiguous pieces — per-leaf
    α collapses to per-bucket α. The shard cap is `bucket_bytes / n`:
    the gather launch reassembles n× its input, so this keeps the moved
    data per launch at the bucket size the GenModel sweep actually
    priced.

    The pieces are 1-D slices, not columns of an (n, Σchunk) matrix: on
    a TPU a matrix with n rows pads its sublane dimension (8 rows for
    f32, 16 for bf16) and its strided column slices relayout the whole
    bucket, which multiplied the step's compile time."""
    import jax.numpy as jnp

    cs = plan.schedule
    k = cs.blocks_per_shard
    buckets = partition([s.size for s in shards],
                        [s.dtype for s in shards],
                        max(1, int(bucket_bytes) // max(1, int(n))),
                        itemsizes=[s.dtype.itemsize for s in shards])
    out = [None] * len(shards)
    for bi, bk in enumerate(buckets):
        row = jnp.concatenate([shards[i].reshape(-1) for i in bk.indices]) \
            if len(bk.indices) > 1 else shards[bk.indices[0]].reshape(-1)
        row = _pad_to(row, k)
        width = row.size
        with scope(f"bucket/zero3_ag{bi}"):
            flat = cs.all_gather(row, plan.axis)
        off = 0
        for i, c in zip(bk.indices, bk.sizes):
            shape, dtype = specs[i]
            count = 1
            for s in shape:
                count *= s
            pieces = [flat[j * width + off:j * width + off + c]
                      for j in range(n)]
            out[i] = (jnp.concatenate(pieces)[:count]
                      .reshape(shape).astype(dtype))
            off += c
    for i, (shape, dtype) in enumerate(specs):
        if out[i] is None:          # empty leaf: nothing was gathered
            out[i] = jnp.zeros(shape, dtype)
    return out


def zero3_scatter_bucketed(fulls, plan, bucket_bytes: int, n: int,
                           reverse: bool = False) -> list:
    """Bucketed gradient ReduceScatter (inverse layout of
    `zero3_gather_bucketed`): each full leaf pads to a multiple of `n`
    and splits into n contiguous chunks; the bucket vector holds, for
    j = 0..n-1, chunk j of every member leaf, so ONE `reduce_scatter`
    launch per bucket returns segment i — the concatenation of every
    member leaf's canonical shard i.

    `reverse=True` issues buckets in reverse-layer readiness order
    (DESIGN.md §15): backward materializes the LAST bucket's gradients
    first, so its reduce leaves the wire without stalling on bucket 0.
    Output placement is by bucket index — results are identical."""
    import jax.numpy as jnp

    cs = plan.schedule
    k = cs.blocks_per_shard
    sizes = [int(x.size) for x in fulls]
    chunks = [(sz + (-sz) % n) // n for sz in sizes]
    buckets = partition(sizes, [x.dtype for x in fulls], bucket_bytes,
                        itemsizes=[x.dtype.itemsize for x in fulls])
    out = [None] * len(fulls)
    issue = list(enumerate(buckets))
    if reverse:
        issue.reverse()
    for bi, bk in issue:
        vecs = [_pad_to(fulls[i].reshape(-1), n) for i in bk.indices]
        width = sum(chunks[i] for i in bk.indices)
        pad = (-width) % k
        pieces = []
        for j in range(n):
            for i, v in zip(bk.indices, vecs):
                pieces.append(v[j * chunks[i]:(j + 1) * chunks[i]])
            if pad:
                pieces.append(jnp.zeros((pad,), vecs[0].dtype))
        with scope(f"bucket/zero3_rs{bi}"):
            shard = cs.reduce_scatter(jnp.concatenate(pieces), plan.axis)
        off = 0
        for i in bk.indices:
            out[i] = shard[off:off + chunks[i]]
            off += chunks[i]
    for i, x in enumerate(fulls):
        if out[i] is None:          # empty leaf: empty shard
            out[i] = jnp.zeros((0,), x.dtype)
    return out


# ---------------------------------------------------------------------------
# Invalidation (elastic remesh / fault-tolerant resume)
# ---------------------------------------------------------------------------
def invalidate_schedules(service=None) -> int:
    """Drop every lowered `CompiledSchedule` and cached bucket plan derived
    from the service's plan cache (the priced plans themselves survive —
    they are placement-independent). Returns the number of artifacts
    dropped. With `service=None` the process-wide default service is
    invalidated *if it exists* (never created just to be emptied).

    Call after any event that changes the executing mesh: an axis-size
    change (`runtime.ft.elastic_remesh`), a fault-tolerant restore onto
    possibly-different hardware (`FaultTolerantLoop`). A stale schedule
    compiled for the old axis size would raise at best (`_check_axis`)
    and silently mis-reduce at worst; after invalidation the next lookup
    re-lowers against the new axis sizes."""
    if service is None:
        from repro.planner.service import peek_default_service
        service = peek_default_service()
        if service is None:
            return 0
    return service.invalidate_executables()
