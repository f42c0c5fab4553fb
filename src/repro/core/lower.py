"""Lowering: block-annotated Plan IR → executable shard_map schedules
(DESIGN.md §8).

`lower_plan` compiles any block-annotated `Plan` (the flat builders in
`core.plans`, GenTree output, baseline plans) into a `CompiledSchedule`:
a sequence of `lax.ppermute` rounds plus N-ary fold phases that runs
inside `shard_map` over a named mesh axis of size plan.n. This closes the
gap between the priced IR and the executed collective — the same Plan the
simulator prices is what the devices run.

Pipeline per synchronized Step:

  1. *expand* — every Transfer/ReduceOp is split into unit-block moves
     (src, dst, block) / folds (dst, block, fan) using the block identity
     recorded by the builders; server ids map to mesh indices through the
     placement map.
  2. *validate* — a symbolic dataflow tracks, per (device, block), the
     bitmask of server contributions held. Fold operands must be pairwise
     disjoint (else: duplicate block reduce), the ReduceOp fan_in must
     match the incoming copies (± the resident copy), and after the final
     step every device must hold every block's full contribution set
     (all-gather completeness). Violations raise `LoweringError` with the
     offending step/server/block.
  3. *schedule* — the step's moves are greedily edge-colored into partial
     permutations (each device sends ≤1 and receives ≤1 block per round —
     a valid `ppermute`), received values land in a staging buffer, and
     fold phases combine staged copies (plus, where the IR says so, the
     device's resident partial) with one N-ary reduction per fold — the
     δ-optimal single-pass fold, routed through the Pallas `fused_reduce`
     kernel when the caller provides it.

The ReduceScatter/AllGather boundary (the step after the last fold) is
detected so ZeRO-3 can run the two halves separately; when num_blocks is
a multiple of n a canonical reorder round is appended so
`reduce_scatter()` yields device i's contiguous shard i (and
`all_gather()` un-reorders before mirroring), matching the flat
collectives' shard contract.

`run_numpy` executes the identical schedule on a (n, size) numpy matrix —
the no-JAX reference used by the hypothesis equivalence suite.
"""
from __future__ import annotations

import sys
import weakref
from dataclasses import dataclass, field
from typing import Callable, Mapping, Sequence

import numpy as np

from repro.runtime.trace import default_tracer, scope

from .plans import Plan


class LoweringError(ValueError):
    """A Plan that cannot be compiled into an executable schedule."""


# ---------------------------------------------------------------------------
# Compiled structures (numpy constants, indexed by mesh position)
# ---------------------------------------------------------------------------
@dataclass(eq=False)
class PermRound:
    """One partial permutation. Each device sends at most one *payload*
    per round — a stack of up to W block rows to a single peer (all the
    step's moves between one (src, dst) pair coalesce into one payload,
    so e.g. RHD's half-vector exchange is ONE ppermute, not size/2 of
    them); -1 entries pad payloads narrower than the round width."""
    perm: tuple[tuple[int, int], ...]   # (src_mesh, dst_mesh) pairs
    send_blks: np.ndarray               # (n, W) block rows sent, -1 = pad
    recv_off: np.ndarray                # (n,) first staging row, -1 = none


@dataclass(eq=False)
class FoldPhase:
    """One fold slot: per device, which staged copies (plus optionally the
    resident partial) collapse into which block row."""
    blk: np.ndarray                     # (n,) target block row, -1 = idle
    ops: np.ndarray                     # (n, K) staging rows, -1 = masked
    include_self: np.ndarray            # (n,) bool: resident partial is an operand


@dataclass(eq=False)
class ExecStep:
    rounds: list[PermRound] = field(default_factory=list)
    n_slots: int = 0
    folds: list[FoldPhase] = field(default_factory=list)


# ---------------------------------------------------------------------------
# Single-round / single-fold executors. `CompiledSchedule._run_steps` loops
# these over one buffer; `core.overlap.MergedSchedule` interleaves them over
# TWO independent buffers (RS-of-bucket-k rounds between AG-of-bucket-(k-1)
# rounds), so merged execution reuses the exact machinery the dataflow
# validation in `lower_plan` vouched for.
# ---------------------------------------------------------------------------
def _round_jax(rd: PermRound, buf, stage, idx, zero, axis_name: str,
               ri: int = 0):
    """One ppermute round: gather up to W block rows of `buf`, permute
    along `axis_name`, land the payload in `stage` at recv_off. Returns
    the updated staging buffer."""
    import jax.numpy as jnp
    from jax import lax

    with scope(f"exec/round{ri}"):
        w = rd.send_blks.shape[1]
        chunk = buf.shape[1]
        sb = jnp.asarray(rd.send_blks)[idx]      # (W,)
        rows = [jnp.where(
            sb[j] >= 0,
            lax.dynamic_index_in_dim(
                buf, jnp.maximum(sb[j], 0), 0, keepdims=False),
            zero) for j in range(w)]
        recv = lax.ppermute(jnp.stack(rows), axis_name,
                            list(rd.perm))  # (W, chunk)
        off = jnp.asarray(rd.recv_off)[idx]
        safe = jnp.maximum(off, 0)
        cur = lax.dynamic_slice(stage, (safe, 0), (w, chunk))
        return lax.dynamic_update_slice(
            stage, jnp.where(off >= 0, recv, cur), (safe, 0))


def _fold_jax(fd: FoldPhase, buf, stage, idx, zero,
              fused_reduce: Callable | None, fi: int = 0):
    """One fold phase: staged copies (plus optionally the resident
    partial) collapse into their target block row of `buf`. Returns the
    updated buffer."""
    import jax.numpy as jnp
    from jax import lax

    with scope(f"exec/fold{fi}"):
        blk = jnp.asarray(fd.blk)[idx]
        safeb = jnp.maximum(blk, 0)
        own = lax.dynamic_index_in_dim(buf, safeb, 0, keepdims=False)
        rows = []
        for j in range(fd.ops.shape[1]):
            s = jnp.asarray(fd.ops[:, j])[idx]
            r = lax.dynamic_index_in_dim(
                stage, jnp.maximum(s, 0), 0, keepdims=False)
            rows.append(jnp.where(s >= 0, r, zero))
        rows.append(jnp.where(
            jnp.asarray(fd.include_self)[idx], own, zero))
        stacked = jnp.stack(rows, axis=0)
        if fused_reduce is not None and stacked.shape[0] > 1:
            folded = fused_reduce(stacked).astype(buf.dtype)
        else:
            folded = stacked.sum(axis=0)
        return lax.dynamic_update_index_in_dim(
            buf, jnp.where(blk >= 0, folded, own), safeb, 0)


@dataclass(eq=False)
class CompiledSchedule:
    """An executable AllReduce: run inside shard_map over `axis_name`."""
    plan_name: str
    n: int
    num_blocks: int
    rs: list[ExecStep]                  # ReduceScatter half
    ag: list[ExecStep]                  # AllGather half
    owner_of_block: np.ndarray          # (num_blocks,) mesh index post-RS
    # canonical-shard support (num_blocks % n == 0): device i's shard is
    # blocks [i*k, (i+1)*k) after the reorder round
    blocks_per_shard: int | None
    reorder: ExecStep | None            # post-RS: owner(b) → b // k
    unorder: ExecStep | None            # pre-AG inverse of `reorder`
    placement: tuple[int, ...]          # server id at each mesh index
    # wire format (cost_model.Precision) for compressed execution: ppermute
    # rounds move quantized payloads + per-tile f32 scales and folds run the
    # fused dequant-reduce. None = full-precision (bit-identical legacy
    # path). The numpy mirror always runs at full precision.
    wire: object | None = None
    # Collective family this schedule computes (plans.FAMILIES). The entry
    # points enforce it: an allgather-family schedule only answers
    # all_gather(), an all_to_all-family one only all_to_all(), etc.
    family: str = "allreduce"
    # p2p family only: the (src_mesh, dst_mesh) edges, for the guard's
    # flat ppermute rung and for introspection.
    perm_pairs: tuple[tuple[int, int], ...] | None = None

    def with_wire(self, precision) -> "CompiledSchedule":
        """A copy of this schedule bound to a wire format (or None to
        strip it). Variants are memoized per wire name: re-resolving the
        same schedule at the same precision returns the SAME object, so
        guard wrappers — memoized per schedule object — keep sticky
        demotion across re-resolves while each wire variant (and the
        full-precision original) still demotes independently."""
        import dataclasses
        if precision is not None and precision.name == "f32":
            precision = None
        if precision is None and self.wire is None:
            return self
        name = precision.name if precision is not None else ""
        variants = self.__dict__.setdefault("_wire_variants", {})
        v = variants.get(name)
        if v is None:
            # replace() copies declared fields only: the variant starts
            # with a clean __dict__ (no inherited guard wrapper / memo)
            v = dataclasses.replace(self, wire=precision)
            variants[name] = v
        return v

    # ---- stats -------------------------------------------------------------
    def total_rounds(self) -> int:
        return sum(len(st.rounds) for st in self.rs + self.ag)

    def describe(self) -> str:
        w = f" wire={self.wire.name}" if self.wire is not None else ""
        f = f" family={self.family}" if self.family != "allreduce" else ""
        return (f"{self.plan_name}: n={self.n} blocks={self.num_blocks} "
                f"steps={len(self.rs)}+{len(self.ag)} "
                f"ppermute_rounds={self.total_rounds()}{w}{f}")

    def _check_family(self, entry: str, allowed: tuple[str, ...]) -> None:
        if self.family not in allowed:
            raise LoweringError(
                f"schedule {self.plan_name!r} compiles a "
                f"{self.family!r}-family plan — {entry}() only runs "
                f"{'/'.join(allowed)} schedules")

    # ---- jax execution (call inside shard_map) -----------------------------
    def _run_steps(self, steps: Sequence[ExecStep], buf, axis_name: str,
                   fused_reduce: Callable | None, phase: str = "steps"):
        # This body runs at shard_map/jit trace time: the scopes name the
        # device ops of each step, round and fold (DESIGN.md §11); the
        # numpy mirror below times the same structure on the host.
        import jax.numpy as jnp
        from jax import lax

        if self.wire is not None:
            return self._run_steps_wire(steps, buf, axis_name, phase)

        idx = lax.axis_index(axis_name)
        chunk = buf.shape[1]
        zero = jnp.zeros((chunk,), buf.dtype)
        for si, st in enumerate(steps):
            if not st.rounds and not st.folds:
                continue
            with scope(f"exec/{phase}/step{si}"):
                stage = jnp.zeros((max(st.n_slots, 1), chunk), buf.dtype)
                for ri, rd in enumerate(st.rounds):
                    stage = _round_jax(rd, buf, stage, idx, zero,
                                       axis_name, ri)
                for fi, fd in enumerate(st.folds):
                    buf = _fold_jax(fd, buf, stage, idx, zero,
                                    fused_reduce, fi)
        return buf

    def _run_steps_wire(self, steps: Sequence[ExecStep], buf,
                        axis_name: str, phase: str = "steps"):
        """Compressed mirror of `_run_steps` (DESIGN.md §13): each ppermute
        round quantizes its payload stack to the wire dtype (per-tile f32
        scales ride in a parallel ppermute), staging buffers hold wire
        bytes, and each fold runs the fused dequant-reduce — operands
        decompress in VMEM, accumulate in f32 with the resident partial,
        and only the folded row lands back in `buf`. Masked rows are
        neutralized by a zero *scale* (dequant of anything × 0 = 0), so
        the pad trick of the full-precision path carries over for free.
        bf16 (scale-free) wires skip the scale plumbing: plain casts."""
        import jax.numpy as jnp
        from jax import lax

        from repro.kernels import ops as kops

        idx = lax.axis_index(axis_name)
        chunk = buf.shape[1]
        wire = self.wire
        wdtype = jnp.dtype(wire.wire_dtype)
        tile = int(wire.scale_block or 0)
        scaled = tile > 0
        if scaled:
            nt = -(-chunk // tile)
            lanes = nt * tile
        else:
            nt, lanes = 0, chunk
        zero = jnp.zeros((chunk,), buf.dtype)
        for si, st in enumerate(steps):
            if not st.rounds and not st.folds:
                continue
            with scope(f"exec/{phase}/step{si}"):
                slots = max(st.n_slots, 1)
                stage_q = jnp.zeros((slots, lanes), wdtype)
                stage_s = (jnp.zeros((slots, nt), jnp.float32)
                           if scaled else None)
                for ri, rd in enumerate(st.rounds):
                    with scope(f"exec/round{ri}"):
                        w = rd.send_blks.shape[1]
                        sb = jnp.asarray(rd.send_blks)[idx]      # (W,)
                        rows = [jnp.where(
                            sb[j] >= 0,
                            lax.dynamic_index_in_dim(
                                buf, jnp.maximum(sb[j], 0), 0,
                                keepdims=False),
                            zero) for j in range(w)]
                        payload = jnp.stack(rows).astype(jnp.float32)
                        if scaled:
                            q, s = kops.quantize(payload, wire.wire_dtype,
                                                 tile)
                            q = lax.ppermute(q, axis_name, list(rd.perm))
                            s = lax.ppermute(s, axis_name, list(rd.perm))
                        else:
                            q = lax.ppermute(payload.astype(wdtype),
                                             axis_name, list(rd.perm))
                            s = None
                        off = jnp.asarray(rd.recv_off)[idx]
                        safe = jnp.maximum(off, 0)
                        cur_q = lax.dynamic_slice(stage_q, (safe, 0),
                                                  (w, lanes))
                        stage_q = lax.dynamic_update_slice(
                            stage_q, jnp.where(off >= 0, q, cur_q),
                            (safe, 0))
                        if scaled:
                            cur_s = lax.dynamic_slice(stage_s, (safe, 0),
                                                      (w, nt))
                            stage_s = lax.dynamic_update_slice(
                                stage_s, jnp.where(off >= 0, s, cur_s),
                                (safe, 0))
                for fi, fd in enumerate(st.folds):
                    with scope(f"exec/fold{fi}"):
                        blk = jnp.asarray(fd.blk)[idx]
                        safeb = jnp.maximum(blk, 0)
                        own = lax.dynamic_index_in_dim(buf, safeb, 0,
                                                       keepdims=False)
                        own_in = jnp.where(
                            jnp.asarray(fd.include_self)[idx], own, zero)
                        qrows, srows = [], []
                        for j in range(fd.ops.shape[1]):
                            si_ = jnp.asarray(fd.ops[:, j])[idx]
                            qr = lax.dynamic_index_in_dim(
                                stage_q, jnp.maximum(si_, 0), 0,
                                keepdims=False)
                            if scaled:
                                sr = lax.dynamic_index_in_dim(
                                    stage_s, jnp.maximum(si_, 0), 0,
                                    keepdims=False)
                                # masked operand → zero scale → decodes 0
                                srows.append(jnp.where(
                                    si_ >= 0, sr,
                                    jnp.zeros((nt,), jnp.float32)))
                                qrows.append(qr)
                            else:
                                qrows.append(jnp.where(
                                    si_ >= 0, qr.astype(jnp.float32),
                                    zero.astype(jnp.float32)))
                        if scaled:
                            folded = kops.quant_reduce(
                                jnp.stack(qrows), jnp.stack(srows),
                                own_in.astype(jnp.float32), tile, chunk)
                        else:
                            folded = jnp.stack(
                                qrows
                                + [own_in.astype(jnp.float32)]).sum(axis=0)
                        folded = folded.astype(buf.dtype)
                        buf = lax.dynamic_update_index_in_dim(
                            buf, jnp.where(blk >= 0, folded, own),
                            safeb, 0)
        return buf

    def _check_axis(self, axis_name: str) -> None:
        from jax import lax
        n = lax.psum(1, axis_name)      # static under shard_map
        if int(n) != self.n:
            raise LoweringError(
                f"schedule {self.plan_name!r} compiled for {self.n} "
                f"devices; mesh axis {axis_name!r} has {int(n)}")

    def allreduce(self, x, axis_name: str, *,
                  fused_reduce: Callable | None = None):
        """Full AllReduce of a per-device array; same shape out."""
        import jax.numpy as jnp
        self._check_family("allreduce", ("allreduce",))
        self._check_axis(axis_name)
        shape = x.shape
        flat = x.reshape(-1)
        pad = (-flat.size) % self.num_blocks
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        buf = flat.reshape(self.num_blocks, -1)
        with scope("exec/allreduce"):
            buf = self._run_steps(self.rs, buf, axis_name, fused_reduce,
                                  phase="rs")
            buf = self._run_steps(self.ag, buf, axis_name, fused_reduce,
                                  phase="ag")
        full = buf.reshape(-1)
        if pad:
            full = full[:-pad]
        return full.reshape(shape)

    def reduce_scatter(self, x, axis_name: str, *,
                       fused_reduce: Callable | None = None):
        """RS half: flat per-device x → canonical shard i on device i."""
        import jax.numpy as jnp
        from jax import lax
        self._check_family("reduce_scatter", ("allreduce", "reduce_scatter"))
        if self.blocks_per_shard is None:
            raise LoweringError(
                f"plan {self.plan_name!r} shards {self.num_blocks} blocks "
                f"over {self.n} devices — no canonical per-device shard; "
                "use allreduce()")
        self._check_axis(axis_name)
        flat = x.reshape(-1)
        pad = (-flat.size) % self.num_blocks
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        buf = flat.reshape(self.num_blocks, -1)
        with scope("exec/reduce_scatter"):
            buf = self._run_steps(self.rs, buf, axis_name, fused_reduce,
                                  phase="rs")
            if self.reorder is not None:
                buf = self._run_steps([self.reorder], buf, axis_name,
                                      None, phase="reorder")
        k = self.blocks_per_shard
        idx = lax.axis_index(axis_name)
        return lax.dynamic_slice_in_dim(buf, idx * k, k, axis=0).reshape(-1)

    def all_gather(self, shard, axis_name: str):
        """AG half: canonical shard i on device i → full flat vector."""
        import jax.numpy as jnp
        from jax import lax
        self._check_family("all_gather", ("allreduce", "allgather"))
        if self.blocks_per_shard is None:
            raise LoweringError(
                f"plan {self.plan_name!r} has no canonical shard layout; "
                "use allreduce()")
        self._check_axis(axis_name)
        k = self.blocks_per_shard
        flat = shard.reshape(-1)
        buf = jnp.zeros((self.num_blocks, flat.size // k), flat.dtype)
        idx = lax.axis_index(axis_name)
        buf = lax.dynamic_update_slice_in_dim(
            buf, flat.reshape(k, -1), idx * k, axis=0)
        with scope("exec/all_gather"):
            if self.unorder is not None:
                buf = self._run_steps([self.unorder], buf, axis_name,
                                      None, phase="unorder")
            buf = self._run_steps(self.ag, buf, axis_name, None,
                                  phase="ag")
        return buf.reshape(-1)

    def all_to_all(self, x, axis_name: str):
        """AllToAll of a per-device operand; same shape out. Semantics
        ≡ `lax.all_to_all(x.reshape(num_blocks, -1), axis, 0, 0)` reshaped
        back: with k = num_blocks / n, device d's output rows
        [s·k, (s+1)·k) are device s's input rows [d·k, (d+1)·k) — the
        standard split-axis-0/concat-axis-0 exchange. Diagonal chunks
        never hit the wire (the lowered plan only ships off-diagonal
        blocks; untouched rows keep the operand value, which IS the
        diagonal). x.size must split into num_blocks equal chunks."""
        if x.size % self.num_blocks:
            raise LoweringError(
                f"all_to_all operand of {x.size} elements does not split "
                f"into {self.num_blocks} equal chunks")
        self._check_family("all_to_all", ("all_to_all",))
        self._check_axis(axis_name)
        shape = x.shape
        buf = x.reshape(self.num_blocks, -1)
        with scope("exec/all_to_all"):
            buf = self._run_steps(self.ag, buf, axis_name, None,
                                  phase="a2a")
        return buf.reshape(shape)

    def p2p(self, x, axis_name: str):
        """Point-to-point exchange: each compiled (src, dst) edge replaces
        dst's buffer with src's payload; devices with no incoming edge
        keep x. Same shape out."""
        self._check_family("p2p", ("p2p",))
        self._check_axis(axis_name)
        shape = x.shape
        buf = x.reshape(1, -1)
        with scope("exec/p2p"):
            buf = self._run_steps(self.ag, buf, axis_name, None,
                                  phase="p2p")
        return buf.reshape(shape)

    # ---- numpy execution (reference; tests) --------------------------------
    def _run_steps_numpy(self, steps: Sequence[ExecStep],
                         buf: np.ndarray,
                         phase: str = "steps") -> np.ndarray:
        # Host spans of the structure the jax path names with scopes; here
        # durations are real — this is the interpreter the equivalence
        # suite runs.
        n = self.n
        tracer = default_tracer()
        for si, st in enumerate(steps):
            with tracer.span(f"exec/{phase}/step", step=si,
                             rounds=len(st.rounds), folds=len(st.folds),
                             plan=self.plan_name):
                stage = np.zeros((n, max(st.n_slots, 1), buf.shape[2]),
                                 buf.dtype)
                for ri, rd in enumerate(st.rounds):
                    with tracer.span("exec/round", round=ri,
                                     width=int(rd.send_blks.shape[1]),
                                     pairs=len(rd.perm)):
                        w = rd.send_blks.shape[1]
                        payload = {}
                        for s, _ in rd.perm:
                            rows = np.zeros((w, buf.shape[2]), buf.dtype)
                            for j, b in enumerate(rd.send_blks[s]):
                                if b >= 0:
                                    rows[j] = buf[s, b]
                            payload[s] = rows
                        for s, d in rd.perm:
                            off = rd.recv_off[d]
                            stage[d, off:off + w] = payload[s]
                for fi, fd in enumerate(st.folds):
                    with tracer.span("exec/fold", fold=fi,
                                     fan=int(fd.ops.shape[1])):
                        new = {}
                        for m in range(n):
                            if fd.blk[m] < 0:
                                continue
                            acc = np.zeros(buf.shape[2], np.float64)
                            for s in fd.ops[m]:
                                if s >= 0:
                                    acc = acc + stage[m, s]
                            if fd.include_self[m]:
                                acc = acc + buf[m, fd.blk[m]]
                            new[m] = acc.astype(buf.dtype)
                        for m, v in new.items():
                            buf[m, fd.blk[m]] = v
        return buf

    def run_numpy(self, X: np.ndarray) -> np.ndarray:
        """Execute on a (n, size) matrix of per-device contributions;
        returns the (n, size) per-device results (all rows == column sums
        for a valid plan). Pure numpy mirror of the jax path."""
        self._check_family("run_numpy", ("allreduce",))
        X = np.asarray(X)
        if X.shape[0] != self.n:
            raise LoweringError(f"expected {self.n} device rows")
        size = X.shape[1]
        pad = (-size) % self.num_blocks
        if pad:
            X = np.concatenate(
                [X, np.zeros((self.n, pad), X.dtype)], axis=1)
        buf = X.reshape(self.n, self.num_blocks, -1).copy()
        with default_tracer().span("exec/run_numpy", plan=self.plan_name,
                                   n=self.n, blocks=self.num_blocks):
            buf = self._run_steps_numpy(self.rs, buf, phase="rs")
            buf = self._run_steps_numpy(self.ag, buf, phase="ag")
        out = buf.reshape(self.n, -1)
        return out[:, :size] if pad else out


# ---------------------------------------------------------------------------
# Compilation helpers
# ---------------------------------------------------------------------------
def _color_rounds(moves: list[tuple[int, int, int]], n: int
                  ) -> tuple[list[PermRound], int, dict[int, int]]:
    """Coalesce the step's moves per (src, dst) pair into one payload
    each, then greedily edge-color the payloads into partial permutations
    (≤1 send and ≤1 receive per device per round). Returns rounds, the
    staging depth, and each move's staging slot keyed by position in
    `moves`. A receiving device reserves the full round width W of
    staging rows (payloads narrower than W pad with zero rows that no
    fold references)."""
    edges: dict[tuple[int, int], list[tuple[int, int]]] = {}
    for mi, (s, d, b) in enumerate(moves):
        edges.setdefault((s, d), []).append((mi, b))
    rounds: list[dict] = []
    for (s, d), items in edges.items():
        for r in rounds:
            if s not in r["senders"] and d not in r["receivers"]:
                break
        else:
            r = {"senders": set(), "receivers": set(), "edges": []}
            rounds.append(r)
        r["senders"].add(s)
        r["receivers"].add(d)
        r["edges"].append((s, d, items))

    slot_of: dict[int, int] = {}
    next_slot = [0] * n
    out = []
    max_w = 0
    for r in rounds:
        w = max(len(items) for _, _, items in r["edges"])
        max_w = max(max_w, w)
        send_blks = np.full((n, w), -1, dtype=np.int64)
        recv_off = np.full(n, -1, dtype=np.int64)
        perm = []
        for s, d, items in sorted(r["edges"]):
            perm.append((s, d))
            for j, (_mi, b) in enumerate(items):
                send_blks[s, j] = b
            recv_off[d] = next_slot[d]
            for j, (mi, _b) in enumerate(items):
                slot_of[mi] = next_slot[d] + j
            next_slot[d] += w
        out.append(PermRound(perm=tuple(perm), send_blks=send_blks,
                             recv_off=recv_off))
    # stage depth must also cover the widest round for devices that
    # receive nothing (their masked dynamic_slice still reads W rows)
    return out, max(max(next_slot, default=0), max_w), slot_of


def _build_folds(groups: dict[tuple[int, int], list[int]],
                 include_self: dict[tuple[int, int], bool],
                 n: int) -> list[FoldPhase]:
    """groups: (dst, blk) → staging slots. Packs each device's fold groups
    into uniform per-device fold phases."""
    per_dev: dict[int, list[tuple[int, list[int], bool]]] = {}
    for (d, b), slots in groups.items():
        per_dev.setdefault(d, []).append((b, slots, include_self[(d, b)]))
    depth = max((len(v) for v in per_dev.values()), default=0)
    width = max((len(slots) for _, slots, _ in
                 (g for v in per_dev.values() for g in v)), default=0)
    folds = []
    for f in range(depth):
        blk = np.full(n, -1, dtype=np.int64)
        ops = np.full((n, max(width, 1)), -1, dtype=np.int64)
        self_mask = np.zeros(n, dtype=bool)
        any_active = False
        for d, gl in per_dev.items():
            if f >= len(gl):
                continue
            b, slots, inc = gl[f]
            blk[d] = b
            ops[d, :len(slots)] = slots
            self_mask[d] = inc
            any_active = True
        if any_active:
            folds.append(FoldPhase(blk=blk, ops=ops,
                                   include_self=self_mask))
    return folds


def _movement_step(moves: list[tuple[int, int, int]], n: int) -> ExecStep:
    """Pure data-movement step (reorder rounds): every receive is a plain
    write of the received block."""
    rounds, n_slots, slot_of = _color_rounds(moves, n)
    groups: dict[tuple[int, int], list[int]] = {}
    inc: dict[tuple[int, int], bool] = {}
    for mi, (s, d, b) in enumerate(moves):
        groups[(d, b)] = [slot_of[mi]]
        inc[(d, b)] = False
    return ExecStep(rounds=rounds, n_slots=n_slots,
                    folds=_build_folds(groups, inc, n))


def _movement_step_remap(moves: list[tuple[int, int, int, int]],
                         n: int) -> ExecStep:
    """Movement step whose writes land at a DIFFERENT block row than the
    one sent: `moves` carries (src, dst, src_block, dst_block). The
    AllToAll lowering uses this — src ships its operand chunk for dst
    (blocks in dst's range), and the copy lands in dst's buffer at src's
    row (the split/concat transpose)."""
    rounds, n_slots, slot_of = _color_rounds(
        [(s, d, sb) for s, d, sb, _ in moves], n)
    groups: dict[tuple[int, int], list[int]] = {}
    inc: dict[tuple[int, int], bool] = {}
    for mi, (s, d, _sb, db) in enumerate(moves):
        groups[(d, db)] = [slot_of[mi]]
        inc[(d, db)] = False
    return ExecStep(rounds=rounds, n_slots=n_slots,
                    folds=_build_folds(groups, inc, n))


def _srv_names(mask: int, inv: Mapping[int, int]) -> list[int]:
    return [inv[m] for m in range(mask.bit_length()) if mask >> m & 1]


def _op_blocks(op, si: int, what: str, nb: int,
               unit: float) -> tuple[int, ...]:
    if op.blocks is None:
        raise LoweringError(
            f"step {si}: {what} {op} is not block-annotated")
    want = len(op.blocks) * unit
    if abs(op.size - want) > 1e-6 * max(1.0, abs(want)):
        raise LoweringError(
            f"step {si}: {what} size {op.size} inconsistent with "
            f"{len(op.blocks)} block(s) of {unit} units")
    for b in op.blocks:
        if not 0 <= b < nb:
            raise LoweringError(
                f"step {si}: {what} names block {b} outside "
                f"0..{nb - 1}")
    return op.blocks


# ---------------------------------------------------------------------------
# lower_plan
# ---------------------------------------------------------------------------
def lower_plan(plan: Plan,
               placement: Sequence[int] | Mapping[int, int] | None = None
               ) -> CompiledSchedule:
    """Compile a block-annotated Plan into an executable CompiledSchedule.

    placement maps server id → mesh index; default: the i-th id of
    sorted(plan.ids()) sits at mesh index i. Raises LoweringError on
    unannotated IR, on structural defects (a server contribution folded
    twice, a fan_in that disagrees with the incoming copies, a block never
    fully reduced, an incomplete final gather) and on placement mismatch.
    """
    if plan.num_blocks is None:
        raise LoweringError(
            f"plan {plan.name!r} carries no block annotations "
            "(Plan.num_blocks is None) — rebuild it with a block-aware "
            "builder before lowering")
    with default_tracer().span("lower/lower_plan", plan=plan.name,
                               n=plan.n, blocks=plan.num_blocks):
        return _lower_plan_inner(plan, placement)


def _lower_plan_inner(plan: Plan,
                      placement: Sequence[int] | Mapping[int, int] | None
                      ) -> CompiledSchedule:
    n = plan.n
    ids = plan.ids()
    if placement is None:
        mesh_of = {sid: i for i, sid in enumerate(sorted(ids))}
    elif isinstance(placement, Mapping):
        mesh_of = {int(k): int(v) for k, v in placement.items()}
    else:
        mesh_of = {int(sid): i for i, sid in enumerate(placement)}
    if sorted(mesh_of.get(sid, -1) for sid in ids) != list(range(n)):
        raise LoweringError(
            f"placement must biject the {n} server ids {sorted(ids)} onto "
            f"mesh indices 0..{n - 1}; got {mesh_of}")
    inv = {m: sid for sid, m in mesh_of.items()}

    if plan.family in ("allgather", "all_to_all", "p2p"):
        return _lower_movement_family(plan, mesh_of, inv)
    if plan.family not in ("allreduce", "reduce_scatter"):
        raise LoweringError(f"unknown plan family {plan.family!r}")

    nb = plan.num_blocks
    unit = plan.size / nb
    full = (1 << n) - 1
    # contrib[mesh][block] = bitmask (over mesh indices) of the server
    # contributions currently summed into that device's copy
    contrib = [[1 << m for _ in range(nb)] for m in range(n)]

    def _blocks_of(op, si: int, what: str) -> tuple[int, ...]:
        return _op_blocks(op, si, what, nb, unit)

    exec_steps: list[ExecStep] = []
    last_fold_step = -1
    for si, st in enumerate(plan.steps):
        moves: list[tuple[int, int, int]] = []
        for t in st.transfers:
            if t.src not in mesh_of or t.dst not in mesh_of:
                raise LoweringError(
                    f"step {si}: transfer {t.src}->{t.dst} uses a server "
                    "id missing from the placement map")
            for b in _blocks_of(t, si, "transfer"):
                moves.append((mesh_of[t.src], mesh_of[t.dst], b))
        fans: dict[tuple[int, int], int] = {}
        for r in st.reduces:
            for b in _blocks_of(r, si, "reduce"):
                key = (mesh_of[r.server], b)
                if key in fans:
                    raise LoweringError(
                        f"step {si}: duplicate reduce of block {b} at "
                        f"server {r.server} — a block may fold at most "
                        "once per server per step")
                fans[key] = r.fan_in

        rounds, n_slots, slot_of = _color_rounds(moves, n)
        groups: dict[tuple[int, int], list[int]] = {}
        opmasks: dict[tuple[int, int], list[int]] = {}
        for mi, (s, d, b) in enumerate(moves):
            groups.setdefault((d, b), []).append(slot_of[mi])
            opmasks.setdefault((d, b), []).append(contrib[s][b])

        include_self: dict[tuple[int, int], bool] = {}
        updates: dict[tuple[int, int], int] = {}
        for key, slots in groups.items():
            d, b = key
            fan = fans.pop(key, None)
            got = len(slots)
            if fan is None:
                if got != 1:
                    raise LoweringError(
                        f"step {si}: server {inv[d]} receives {got} "
                        f"copies of block {b} with no reduce — ambiguous "
                        "write")
                include_self[key] = False
                updates[key] = opmasks[key][0]
                continue
            if fan == got:
                inc = False
            elif fan == got + 1:
                inc = True
            else:
                raise LoweringError(
                    f"step {si}: reduce of block {b} at server {inv[d]} "
                    f"declares fan_in={fan} but {got} copies arrive "
                    f"(expected fan_in of {got} or {got + 1})")
            include_self[key] = inc
            acc = contrib[d][b] if inc else 0
            for om, s_slot in zip(opmasks[key], slots):
                if acc & om:
                    dup = _srv_names(acc & om, inv)
                    raise LoweringError(
                        f"step {si}: duplicate block reduce — "
                        f"contribution(s) of server(s) {dup} to block {b} "
                        f"fold twice at server {inv[d]}")
                acc |= om
            updates[key] = acc
        if fans:
            (d, b), fan = next(iter(fans.items()))
            raise LoweringError(
                f"step {si}: reduce of block {b} at server {inv[d]} "
                f"(fan_in={fan}) has no incoming copies")
        for (d, b), mask in updates.items():
            contrib[d][b] = mask
        if st.reduces:
            last_fold_step = si
        exec_steps.append(ExecStep(
            rounds=rounds, n_slots=n_slots,
            folds=_build_folds(groups, include_self, n)))

        if si == last_fold_step:
            rs_contrib = [row[:] for row in contrib]

    # ---- completeness ------------------------------------------------------
    if last_fold_step < 0:
        raise LoweringError(
            f"plan {plan.name!r} contains no reduces — not "
            f"{'an AllReduce' if plan.family == 'allreduce' else 'a ReduceScatter'}")
    if plan.family == "allreduce":
        for m in range(n):
            for b in range(nb):
                if contrib[m][b] != full:
                    missing = _srv_names(full & ~contrib[m][b], inv)
                    raise LoweringError(
                        f"incomplete gather: server {inv[m]} ends without "
                        f"the contribution(s) of server(s) {missing} for "
                        f"block {b}")
    else:
        # reduce_scatter family: the ownership layout is the END state —
        # trailing movement steps (a builder's own reorder) count.
        rs_contrib = [row[:] for row in contrib]

    # ---- ReduceScatter boundary + canonical shard layout -------------------
    owner = np.full(nb, -1, dtype=np.int64)
    for b in range(nb):
        holders = [m for m in range(n) if rs_contrib[m][b] == full]
        if not holders:
            parts = {m: _srv_names(rs_contrib[m][b], inv)
                     for m in range(n) if rs_contrib[m][b]}
            raise LoweringError(
                f"block {b} is never fully reduced by the end of the "
                f"ReduceScatter phase (step {last_fold_step}); partial "
                f"holders: {parts}")
        owner[b] = holders[0]

    blocks_per_shard = nb // n if nb % n == 0 else None
    reorder = unorder = None
    if blocks_per_shard:
        k = blocks_per_shard
        fwd = [(int(owner[b]), b // k, b) for b in range(nb)
               if int(owner[b]) != b // k]
        if fwd:
            reorder = _movement_step(fwd, n)
            unorder = _movement_step([(d, s, b) for s, d, b in fwd], n)

    if plan.family == "reduce_scatter":
        # every step belongs to the RS half; nothing gathers afterwards
        rs_steps, ag_steps = exec_steps, []
    else:
        rs_steps = exec_steps[:last_fold_step + 1]
        ag_steps = exec_steps[last_fold_step + 1:]
    return CompiledSchedule(
        plan_name=plan.name, n=n, num_blocks=nb,
        rs=rs_steps, ag=ag_steps,
        owner_of_block=owner, blocks_per_shard=blocks_per_shard,
        reorder=reorder, unorder=unorder,
        placement=tuple(inv[m] for m in range(n)),
        family=plan.family)


def _lower_movement_family(plan: Plan, mesh_of: Mapping[int, int],
                           inv: Mapping[int, int]) -> CompiledSchedule:
    """Lower a fold-free family (allgather / all_to_all / p2p).

    allgather: each block's initial holder is INFERRED from the steps — a
    server that sends a block before ever receiving it must have started
    with it. Exactly one initial holder per block is required (the
    `all_gather()` entry seeds the canonical shard and `unorder` ships
    each block to that holder, so a second presumed holder would forward
    garbage), and every server must end holding every block.

    all_to_all: every transfer must ship blocks from the sender's operand
    chunk for the destination (block b of src→dst needs dst·k ≤ b <
    (dst+1)·k, k = num_blocks/n); the copy lands at dst row
    src·k + (b − dst·k) — the split-0/concat-0 transpose. Completeness:
    every off-diagonal row received exactly once. Only direct (single-hop)
    plans lower; a hierarchical AllToAll prices fine but fails the chunk
    check here by construction.

    p2p: arbitrary edges, full buffer each; at most one incoming edge per
    receiver per step. The edge list is kept on the schedule
    (`perm_pairs`) for the guard's flat rung."""
    n, nb, family = plan.n, plan.num_blocks, plan.family
    unit = plan.size / nb
    exec_steps: list[ExecStep] = []

    def _expand(st, si):
        moves: list[tuple[int, int, int]] = []
        if st.reduces:
            raise LoweringError(
                f"step {si}: a {family!r}-family plan cannot fold "
                f"(found {len(st.reduces)} reduce op(s))")
        for t in st.transfers:
            if t.src not in mesh_of or t.dst not in mesh_of:
                raise LoweringError(
                    f"step {si}: transfer {t.src}->{t.dst} uses a server "
                    "id missing from the placement map")
            for b in _op_blocks(t, si, "transfer", nb, unit):
                moves.append((mesh_of[t.src], mesh_of[t.dst], b))
        return moves

    if family == "allgather":
        holds = [[False] * nb for _ in range(n)]
        initial = [[False] * nb for _ in range(n)]
        for si, st in enumerate(plan.steps):
            moves = _expand(st, si)
            seen_writes: set[tuple[int, int]] = set()
            for s, d, b in moves:
                if not holds[s][b]:
                    for m in range(n):
                        if initial[m][b]:
                            raise LoweringError(
                                f"step {si}: block {b} would need to start "
                                f"at both server {inv[m]} and server "
                                f"{inv[s]} — ambiguous initial holder")
                    holds[s][b] = True
                    initial[s][b] = True
                if (d, b) in seen_writes:
                    raise LoweringError(
                        f"step {si}: server {inv[d]} receives block {b} "
                        "twice — ambiguous write")
                seen_writes.add((d, b))
            exec_steps.append(_movement_step(moves, n))
            for _s, d, b in moves:
                holds[d][b] = True
        owner = np.full(nb, -1, dtype=np.int64)
        for b in range(nb):
            src = [m for m in range(n) if initial[m][b]]
            if not src:
                if n == 1:
                    owner[b] = 0
                    continue
                raise LoweringError(
                    f"block {b} is never transferred — no initial holder "
                    "to gather it from")
            owner[b] = src[0]
            for m in range(n):
                if not holds[m][b]:
                    raise LoweringError(
                        f"incomplete gather: server {inv[m]} ends without "
                        f"block {b}")
        blocks_per_shard = nb // n if nb % n == 0 else None
        reorder = unorder = None
        if blocks_per_shard:
            k = blocks_per_shard
            fwd = [(int(owner[b]), b // k, b) for b in range(nb)
                   if int(owner[b]) != b // k]
            if fwd:
                reorder = _movement_step(fwd, n)
                unorder = _movement_step([(d, s, b) for s, d, b in fwd], n)
        return CompiledSchedule(
            plan_name=plan.name, n=n, num_blocks=nb, rs=[], ag=exec_steps,
            owner_of_block=owner, blocks_per_shard=blocks_per_shard,
            reorder=reorder, unorder=unorder,
            placement=tuple(inv[m] for m in range(n)), family=family)

    if family == "all_to_all":
        if nb % n:
            raise LoweringError(
                f"all_to_all plan {plan.name!r} needs num_blocks ({nb}) "
                f"divisible by n ({n})")
        k = nb // n
        received: set[tuple[int, int]] = set()
        for si, st in enumerate(plan.steps):
            moves4: list[tuple[int, int, int, int]] = []
            for s, d, b in _expand(st, si):
                if not d * k <= b < (d + 1) * k:
                    raise LoweringError(
                        f"step {si}: transfer {inv[s]}->{inv[d]} ships "
                        f"block {b} outside the destination chunk "
                        f"[{d * k}, {(d + 1) * k}) — only direct "
                        "(single-hop) all_to_all plans lower")
                row = s * k + (b - d * k)
                if (d, row) in received:
                    raise LoweringError(
                        f"step {si}: server {inv[d]} receives output row "
                        f"{row} twice — ambiguous write")
                received.add((d, row))
                moves4.append((s, d, b, row))
            exec_steps.append(_movement_step_remap(moves4, n))
        for d in range(n):
            for s in range(n):
                if s == d:
                    continue    # diagonal chunk never hits the wire
                for j in range(k):
                    if (d, s * k + j) not in received:
                        raise LoweringError(
                            f"incomplete all_to_all: server {inv[d]} never "
                            f"receives row {s * k + j} (chunk of server "
                            f"{inv[s]})")
        return CompiledSchedule(
            plan_name=plan.name, n=n, num_blocks=nb, rs=[], ag=exec_steps,
            owner_of_block=np.arange(nb, dtype=np.int64) // k,
            blocks_per_shard=None, reorder=None, unorder=None,
            placement=tuple(inv[m] for m in range(n)), family=family)

    # p2p
    pairs: list[tuple[int, int]] = []
    for si, st in enumerate(plan.steps):
        moves = _expand(st, si)
        dsts: set[int] = set()
        for s, d, _b in moves:
            if d in dsts:
                raise LoweringError(
                    f"step {si}: server {inv[d]} receives two p2p "
                    "payloads — ambiguous write")
            dsts.add(d)
            pairs.append((s, d))
        exec_steps.append(_movement_step(moves, n))
    return CompiledSchedule(
        plan_name=plan.name, n=n, num_blocks=nb, rs=[], ag=exec_steps,
        owner_of_block=np.zeros(nb, dtype=np.int64),
        blocks_per_shard=None, reorder=None, unorder=None,
        placement=tuple(inv[m] for m in range(n)), family=family,
        perm_pairs=tuple(pairs))


# ---------------------------------------------------------------------------
# Guarded execution (DESIGN.md §12)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class GuardPolicy:
    """Launch guard knobs for GuardedSchedule.

    `timeout` is a *post-hoc* budget: schedule launches happen at trace
    time inside shard_map, so an in-flight dispatch cannot be aborted —
    a launch that overruns the budget still returns its (valid) result,
    but the guard counts the timeout and demotes subsequent launches to
    the flat fallback rung. `max_retries` bounds re-attempts of the
    planned rung with exponential `backoff` (seconds, doubling, capped
    at 2 s). `fallback=False` turns the ladder's flat rung off: the last
    error is raised instead."""
    timeout: float | None = None
    max_retries: int = 1
    backoff: float = 0.05
    fallback: bool = True


class GuardedSchedule:
    """Fallback-laddered wrapper around a CompiledSchedule.

    Ladder per launch: planned schedule (with bounded retry) → flat jax
    collective (`lax.psum` / psum+slice / `lax.all_gather`) → raise.
    Every rung transition is counted in the metrics registry
    (`guarded_*_total`) and opens a telemetry re-measure window
    (`Telemetry.remeasure`) — a fallback means measurements of the
    planned schedule stopped describing what actually ran. After a
    fallback or timeout the guard *demotes*: subsequent launches take
    the flat rung directly (sticky, cleared by `reset_guard`), so a
    persistently failing schedule costs one failed attempt, not one per
    step. An armed `runtime.faults` injector is consulted before each
    planned-rung attempt (`check_launch`), which is how chaos tests
    exercise the ladder deterministically.

    Everything not guarded (describe, blocks_per_shard, run_numpy-less
    attrs, …) delegates to the wrapped schedule, so the wrapper is a
    drop-in anywhere a CompiledSchedule flows (core.bucketing probes
    `blocks_per_shard` via getattr; collectives compare by identity).
    """

    def __init__(self, schedule, *, policy: GuardPolicy | None = None,
                 telemetry=None):
        self.inner = schedule
        self.policy = policy or GuardPolicy()
        self.telemetry = telemetry
        self._demoted = False
        self._wire_demoted = False
        self._full = None               # lazy full-precision rung
        self.stats = {"launches": 0, "retries": 0, "fallbacks": 0,
                      "timeouts": 0, "demoted_launches": 0,
                      "wire_fallbacks": 0, "wire_demoted_launches": 0,
                      "reprobes": 0}
        _GUARD_REGISTRY.add(self)

    def __getattr__(self, name):
        inner = self.__dict__.get("inner")
        if inner is None:
            raise AttributeError(name)
        return getattr(inner, name)

    @property
    def demoted(self) -> bool:
        return self._demoted

    @property
    def wire_demoted(self) -> bool:
        return self._wire_demoted

    def reset_guard(self) -> None:
        self._demoted = False
        self._wire_demoted = False

    # -- internals ----------------------------------------------------------
    def _metrics(self):
        from repro.runtime.metrics import default_metrics
        return default_metrics()

    def _remeasure(self, reason: str, info: dict) -> None:
        tele = self.telemetry
        if tele is None:
            from repro.runtime.telemetry import peek_default_telemetry
            tele = peek_default_telemetry()
        if tele is not None:
            tele.remeasure(reason, info)

    def _note_fallback(self, what: str, err) -> None:
        print(f"fallback: planned {self.inner.plan_name} {what} -> flat "
              f"lax collective ({err!r})", file=sys.stderr, flush=True)
        self.stats["fallbacks"] += 1
        self._demoted = True
        self._metrics().counter(
            "guarded_fallbacks_total",
            "guarded launches demoted to the flat collective rung").inc()
        default_tracer().instant("guard/fallback", plan=self.inner.plan_name,
                                 what=what, error=repr(err))
        self._remeasure("guard_fallback",
                        {"plan": self.inner.plan_name, "what": what,
                         "error": repr(err)})

    def _full_rung(self):
        """The full-precision planned rung of a compressed schedule: the
        same CompiledSchedule with the wire stripped (lazy, cached)."""
        if self._full is None:
            self._full = self.inner.with_wire(None)
        return self._full

    def _note_wire_fallback(self, what: str, err) -> None:
        print(f"fallback: compressed {self.inner.wire.name} "
              f"{self.inner.plan_name} {what} -> full precision ({err!r})",
              file=sys.stderr, flush=True)
        self.stats["wire_fallbacks"] += 1
        self._wire_demoted = True
        self._metrics().counter(
            "guarded_wire_fallbacks_total",
            "compressed launches demoted to the full-precision rung").inc()
        default_tracer().instant("guard/wire_fallback",
                                 plan=self.inner.plan_name, what=what,
                                 wire=self.inner.wire.name, error=repr(err))
        self._remeasure("guard_wire_fallback",
                        {"plan": self.inner.plan_name, "what": what,
                         "wire": self.inner.wire.name, "error": repr(err)})

    def _guarded_wire(self, what: str, attempt, mid, fallback):
        """Top rung of the compressed ladder (DESIGN.md §13): one attempt
        at the wire schedule — a failure demotes (sticky) to the full-
        precision planned rung, which keeps `_guarded`'s own retry/flat
        ladder below it. compressed → full-precision → flat psum."""
        m = self._metrics()
        if not self._wire_demoted:
            self.stats["launches"] += 1
            m.counter("guarded_launches_total",
                      "collective launches through the schedule guard"
                      ).inc()
            try:
                from repro.runtime.faults import active_injector
                inj = active_injector()
                if inj is not None:
                    inj.check_launch(f"{self.inner.plan_name}/{what}")
                return attempt()
            except Exception as e:        # noqa: BLE001 — ladder rung
                self._note_wire_fallback(what, e)
                return self._guarded(what, mid, fallback)
        self.stats["wire_demoted_launches"] += 1
        m.counter("guarded_wire_demoted_launches_total",
                  "launches served at full precision after wire demotion"
                  ).inc()
        return self._guarded(what, mid, fallback)

    def _guarded(self, what: str, attempt, fallback):
        import time as _time
        m = self._metrics()
        self.stats["launches"] += 1
        m.counter("guarded_launches_total",
                  "collective launches through the schedule guard").inc()
        pol = self.policy
        if self._demoted and fallback is not None and pol.fallback:
            self.stats["demoted_launches"] += 1
            m.counter("guarded_demoted_launches_total",
                      "launches served by the flat rung after demotion"
                      ).inc()
            return fallback()
        err = None
        for attempt_i in range(pol.max_retries + 1):
            if attempt_i:
                self.stats["retries"] += 1
                m.counter("guarded_retries_total",
                          "planned-rung retry attempts").inc()
                if pol.backoff > 0:
                    _time.sleep(min(pol.backoff * (2 ** (attempt_i - 1)),
                                    2.0))
            try:
                from repro.runtime.faults import active_injector
                inj = active_injector()
                if inj is not None:
                    inj.check_launch(f"{self.inner.plan_name}/{what}")
                t0 = _time.perf_counter()
                out = attempt()
                dt = _time.perf_counter() - t0
                if pol.timeout is not None and dt > pol.timeout:
                    # dispatch already completed — result is valid, but
                    # demote so the next launch takes the flat rung
                    self.stats["timeouts"] += 1
                    self._demoted = True
                    m.counter("guarded_timeouts_total",
                              "launches exceeding the per-launch budget"
                              ).inc()
                    self._remeasure("guard_timeout",
                                    {"plan": self.inner.plan_name,
                                     "what": what, "dt": dt,
                                     "budget": pol.timeout})
                return out
            except Exception as e:            # noqa: BLE001 — ladder rung
                err = e
        if fallback is not None and pol.fallback:
            self._note_fallback(what, err)
            return fallback()
        raise err

    # -- guarded collective surface -----------------------------------------
    def allreduce(self, x, axis_name: str, *,
                  fused_reduce: Callable | None = None):
        from jax import lax
        attempt = lambda: self.inner.allreduce(  # noqa: E731
            x, axis_name, fused_reduce=fused_reduce)
        flat = lambda: lax.psum(x, axis_name)    # noqa: E731
        if getattr(self.inner, "wire", None) is not None:
            return self._guarded_wire(
                "allreduce", attempt,
                lambda: self._full_rung().allreduce(
                    x, axis_name, fused_reduce=fused_reduce),
                flat)
        return self._guarded("allreduce", attempt, flat)

    def reduce_scatter(self, x, axis_name: str, *,
                       fused_reduce: Callable | None = None):
        def flat_rs():
            # mirror the inner contract: pad to the block multiple, full
            # psum, take this device's canonical shard
            import jax.numpy as jnp
            from jax import lax
            flat = x.reshape(-1)
            pad = (-flat.size) % self.inner.num_blocks
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), flat.dtype)])
            full = lax.psum(flat, axis_name)
            k = full.size // self.inner.n
            idx = lax.axis_index(axis_name)
            return lax.dynamic_slice_in_dim(full, idx * k, k)

        attempt = lambda: self.inner.reduce_scatter(  # noqa: E731
            x, axis_name, fused_reduce=fused_reduce)
        if getattr(self.inner, "wire", None) is not None:
            return self._guarded_wire(
                "reduce_scatter", attempt,
                lambda: self._full_rung().reduce_scatter(
                    x, axis_name, fused_reduce=fused_reduce),
                flat_rs)
        return self._guarded("reduce_scatter", attempt, flat_rs)

    def all_gather(self, shard, axis_name: str):
        def flat_ag():
            from jax import lax
            return lax.all_gather(shard.reshape(-1), axis_name, axis=0,
                                  tiled=True)

        attempt = lambda: self.inner.all_gather(  # noqa: E731
            shard, axis_name)
        if getattr(self.inner, "wire", None) is not None:
            return self._guarded_wire(
                "all_gather", attempt,
                lambda: self._full_rung().all_gather(shard, axis_name),
                flat_ag)
        return self._guarded("all_gather", attempt, flat_ag)

    def all_to_all(self, x, axis_name: str):
        def flat_a2a():
            from jax import lax
            nb = self.inner.num_blocks
            return lax.all_to_all(x.reshape(nb, -1), axis_name, 0,
                                  0).reshape(x.shape)

        attempt = lambda: self.inner.all_to_all(x, axis_name)  # noqa: E731
        if getattr(self.inner, "wire", None) is not None:
            return self._guarded_wire(
                "all_to_all", attempt,
                lambda: self._full_rung().all_to_all(x, axis_name),
                flat_a2a)
        return self._guarded("all_to_all", attempt, flat_a2a)

    def p2p(self, x, axis_name: str):
        def flat_p2p():
            import jax.numpy as jnp
            from jax import lax
            pairs = list(self.inner.perm_pairs or ())
            if not pairs:
                return x
            recv = lax.ppermute(x, axis_name, pairs)
            has_in = np.zeros(self.inner.n, dtype=bool)
            for _s, d in pairs:
                has_in[d] = True
            idx = lax.axis_index(axis_name)
            return jnp.where(jnp.asarray(has_in)[idx], recv, x)

        attempt = lambda: self.inner.p2p(x, axis_name)  # noqa: E731
        if getattr(self.inner, "wire", None) is not None:
            return self._guarded_wire(
                "p2p", attempt,
                lambda: self._full_rung().p2p(x, axis_name),
                flat_p2p)
        return self._guarded("p2p", attempt, flat_p2p)

    def run_numpy(self, X: np.ndarray) -> np.ndarray:
        # reference path: guard machinery applies (bench measures its
        # overhead here) but there is no flat numpy rung — errors raise
        return self._guarded("run_numpy",
                             lambda: self.inner.run_numpy(X), None)


# Every live guard, for health-restoration re-probes. Guards stay alive
# exactly as long as their schedule (guard_schedule memoizes the wrapper
# on the schedule object), so a WeakSet tracks precisely the schedules
# still cached somewhere.
_GUARD_REGISTRY: "weakref.WeakSet[GuardedSchedule]" = weakref.WeakSet()


def reprobe_guards(reason: str = "health_restore") -> int:
    """Re-arm every live demoted guard (DESIGN.md §12): sticky demotion
    exists so a *persistently* failing schedule costs one failed attempt
    instead of one per step — but after a `link_restore` / remesh the
    fault that caused the demotion is gone, and staying pinned to the
    flat rung forever forfeits the planned schedule's speedup.
    `PlannerService.mark_degraded(level, factor >= 1)` (the restore path
    `runtime.ft` drives on link_restore events) and `clear_degraded` call
    this, so the next launch re-probes the planned (and compressed) rung.
    Returns the number of guards re-armed."""
    cleared = 0
    for g in list(_GUARD_REGISTRY):
        if g._demoted or g._wire_demoted:
            g.reset_guard()
            g.stats["reprobes"] += 1
            cleared += 1
    if cleared:
        from repro.runtime.metrics import default_metrics
        default_metrics().counter(
            "guarded_reprobes_total",
            "demoted guards re-armed by health-restoration events"
        ).inc(cleared)
        default_tracer().instant("guard/reprobe", reason=reason,
                                 cleared=cleared)
    return cleared


def guard_stats() -> list[tuple[str, dict]]:
    """(plan name, stats) of every live guard: a run that must not have
    fallen back checks `fallbacks`, `wire_fallbacks`, `demoted_launches`
    and `timeouts` here."""
    return [(g.inner.plan_name, dict(g.stats)) for g in list(_GUARD_REGISTRY)]


def guard_schedule(schedule, *, telemetry=None, policy=None):
    """Memoized GuardedSchedule for `schedule`: repeated calls (one per
    train step on the bucketed path) return the SAME wrapper, so sticky
    demotion and guard stats survive across launches instead of being
    reset by every re-wrap. Idempotent on an already-guarded schedule."""
    if schedule is None or isinstance(schedule, GuardedSchedule):
        return schedule
    g = getattr(schedule, "_guard_wrapper", None)
    if g is None:
        g = GuardedSchedule(schedule, telemetry=telemetry, policy=policy)
        try:
            schedule._guard_wrapper = g
        except (AttributeError, TypeError):
            pass                      # unwritable object: unmemoized wrap
    return g
