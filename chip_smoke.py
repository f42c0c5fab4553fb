#!/usr/bin/env python3
"""Bring-up check of the main path on a TPU.

One chip (no arguments):
  1. the Mosaic `wkv` kernel against the chunked jnp recurrence
     (`models.recurrence._wkv_chunk`) at the rwkv6-1.6b step's shapes;
  2. the Mosaic `quant_reduce` kernel against `kernels/ref.py` on a
     bucket-sized row (fan-in 4, 4 Mi f32 lanes) for both scaled wires;
  3. a few steps of the rwkv6-1.6b trainer at its published widths, depth
     cut to ONE_CHIP_LAYERS, through `run_training` with the ZeRO-3
     engine and planner-lowered sync (`--engine manual --sync plan`).

Four chips (`--four-chips`), and nothing else:
  1. planned AllReduce / ReduceScatter / AllGather
     (`PlannerService.get_axis_executable`) and AllToAll
     (`get_family_executable`) against their `lax` collectives on a 4-device
     axis, 4 KiB to 64 MiB per device, plus one int8-wire AllReduce;
  2. the same trainer under ZeRO-3 across the four chips at
     FOUR_CHIP_LAYERS, once with `--sync plan` and once with `--sync psum`
     from the same seed; the losses must agree.

Either way the run fails unless every loss is finite, no schedule guard or
sync path fell back, and JAX's first device is a TPU. There is no CPU path.
The last line of standard output is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Usage:  python3 chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "rwkv6-1.6b"
SEQ_LEN = 4096            # the config's train_4k shape
STEPS = 3
# Depth cuts, from `python -m benchmarks.step_memory` (the step compiled
# for a described v5e; per-device live bytes = args + temps + outputs -
# aliased; the chip's compiler refuses a step above 15.75 GiB). The run
# prints the same analysis of the step it compiled on the chip.
# One chip, all of the state on it: 10 layers take 14.71 GiB.
ONE_CHIP_LAYERS = 10
# ZeRO-3 over a 2x2 host, the full depth: 15.11 GiB per chip with
# --sync plan, 13.34 GiB with --sync psum.
FOUR_CHIP_LAYERS = 24

WKV_TOL = 1e-4            # f32 on both sides, matmuls at HIGHEST precision
QUANT_TOL = 1e-6          # same f32 products, summed in another order
COLL_TOL = 1e-5           # f32 planned schedule vs lax, relative
# plan vs psum losses: bf16 parameters and gradients, reduced in another
# order, drift apart by bf16 rounding from the first update on
LOSS_TOL = 5e-2

FALLBACK_COUNTERS = (
    "guarded_fallbacks_total", "guarded_wire_fallbacks_total",
    "guarded_demoted_launches_total", "guarded_wire_demoted_launches_total",
    "guarded_timeouts_total", "sync_fallbacks_total")
FALLBACK_STATS = ("fallbacks", "wire_fallbacks", "demoted_launches",
                  "wire_demoted_launches", "timeouts")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    yield
    log(f"phase {name}: {time.perf_counter() - t0:.1f} s wall")


def relerr(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


class CompileClock:
    """Sums JAX's own compile-duration events and counts persistent
    compilation-cache hits and misses."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def check_wkv(cfg) -> None:
    """Mosaic wkv vs the chunked jnp recurrence on the chip, at the step's
    (B=1, H, T, head) shapes."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.wkv import wkv
    from repro.models.recurrence import _wkv_chunk

    B, H, T, K = 1, cfg.n_heads, SEQ_LEN, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    r, k, v = (jax.random.normal(x, (B, H, T, K), jnp.float32) * 0.5
               for x in ks[:3])
    # the model's decay: log w = -exp(w0 + lora), w0 ~ N(-2, 0.5)
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, K)) * 0.5 - 2.0)
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    s0 = jnp.zeros((B, H, K, K), jnp.float32)
    got, s_got = jax.jit(lambda *a: wkv(*a, chunk=32))(r, k, v, logw, u, s0)
    with jax.default_matmul_precision("highest"):
        want, s_want = jax.jit(lambda *a: _wkv_chunk(*a, 32))(
            r, k, v, logw, u, s0)
    e_out, e_state = relerr(got, want), relerr(s_got, s_want)
    log(f"wkv kernel vs _wkv_chunk (B={B} H={H} T={T} K={K}): "
        f"rel err out {e_out:.3e}, state {e_state:.3e} (tol {WKV_TOL:g})")
    check(e_out < WKV_TOL and e_state < WKV_TOL, "wkv kernel disagrees")


def check_quant() -> None:
    """Mosaic quant_reduce vs kernels/ref.py on a bucket-sized row."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.quant import quant_reduce

    K, L = 4, 4 << 20
    x = jax.random.normal(jax.random.PRNGKey(1), (K, L), jnp.float32)
    own = jax.random.normal(jax.random.PRNGKey(2), (L,), jnp.float32)
    for wire in ("int8", "float8_e4m3fn"):
        q, s = jax.jit(lambda a, w=wire: ref.quantize_ref(a, w))(x)
        got = jax.jit(lambda q, s, o: quant_reduce(q, s, o, out_len=L))(
            q, s, own)
        want = jax.jit(lambda q, s, o: ref.quant_reduce_ref(
            q, s, o, 128, L))(q, s, own)
        err = relerr(got, want)
        log(f"quant_reduce kernel vs ref ({wire}, fan-in {K}, {L} lanes): "
            f"rel err {err:.3e} (tol {QUANT_TOL:g})")
        check(err < QUANT_TOL, f"quant_reduce ({wire}) disagrees")


def train(layers: int, sync: str, global_batch: int) -> dict:
    from repro.launch.train import TrainConfig, run_training
    out = run_training(TrainConfig(
        arch=ARCH, layers=layers, steps=STEPS, seq_len=SEQ_LEN,
        global_batch=global_batch, engine="manual", sync=sync, lr=1e-4,
        seed=0, log_every=1), on_log=log)
    losses = out["losses"]
    sec = out["step_seconds"]
    steady = sorted(sec[1:])[len(sec[1:]) // 2] if len(sec) > 1 else sec[0]
    log(f"trainer sync={sync}: first step {sec[0]:.3f} s, median later "
        f"step {steady:.3f} s, losses {losses}")
    check(len(losses) == STEPS and all(math.isfinite(x) for x in losses),
          f"non-finite or missing losses with sync={sync}: {losses}")
    return out


def check_no_fallback() -> None:
    from repro.core.lower import guard_stats
    from repro.runtime.metrics import default_metrics

    snap = default_metrics().snapshot()
    counters = {name: snap.get(name, {}).get("value", 0.0)
                for name in FALLBACK_COUNTERS}
    fired = {k: v for k, v in counters.items() if v}
    bad = [(plan, {k: st[k] for k in FALLBACK_STATS if st.get(k)})
           for plan, st in guard_stats()
           if any(st.get(k) for k in FALLBACK_STATS)]
    log(f"fallbacks: counters {fired or 'none'}; guards "
        f"{len(guard_stats())} live, {len(bad)} with fallbacks")
    check(not fired and not bad, f"a fallback fired: {fired} {bad}")


def one_chip() -> None:
    from repro.configs import get_config

    with phase("wkv kernel"):
        check_wkv(get_config(ARCH))
    with phase("quant kernel"):
        check_quant()
    with phase("trainer"):
        train(ONE_CHIP_LAYERS, "plan", global_batch=1)
    check_no_fallback()


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def check_collectives(n: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core.cost_model import PRECISIONS
    from repro.core.lower import guard_schedule
    from repro.launch.mesh import make_mesh
    from repro.planner.service import PlannerService

    mesh = make_mesh((n,), ("x",))
    svc = PlannerService()

    def run(body, x):
        f = jax.jit(shard_map(lambda v: body(v[0])[None], mesh=mesh,
                              in_specs=P("x"), out_specs=P("x")))
        return f(x)

    for kib in (4, 64, 1024, 16384, 65536):
        size = kib * 1024 // 4                      # f32 per device
        x = jax.random.normal(jax.random.PRNGKey(kib), (n, size),
                              jnp.float32)
        ar = guard_schedule(svc.get_axis_executable("x", n, size).schedule)
        rs = guard_schedule(svc.get_family_executable(
            "reduce_scatter", "x", n, size).schedule)
        ag = guard_schedule(svc.get_family_executable(
            "allgather", "x", n, size).schedule)
        a2a = guard_schedule(svc.get_family_executable(
            "all_to_all", "x", n, size).schedule)
        shard = x[:, :size // n]
        errs = {
            "allreduce": relerr(
                run(lambda v: ar.allreduce(v, "x"), x),
                run(lambda v: lax.psum(v, "x"), x)),
            "reduce_scatter": relerr(
                run(lambda v: rs.reduce_scatter(v, "x"), x),
                run(lambda v: lax.psum_scatter(v, "x", tiled=True), x)),
            "allgather": relerr(
                run(lambda v: ag.all_gather(v, "x"), shard),
                run(lambda v: lax.all_gather(v, "x", tiled=True), shard)),
            "all_to_all": relerr(
                run(lambda v: a2a.all_to_all(v, "x"), x),
                run(lambda v: lax.all_to_all(
                    v.reshape(n, -1), "x", 0, 0).reshape(v.shape), x)),
        }
        log(f"collectives {kib} KiB/device (allreduce plan "
            f"{ar.plan_name}): "
            + ", ".join(f"{k} rel err {e:.2e}" for k, e in errs.items()))
        check(all(e < COLL_TOL for e in errs.values()),
              f"planned collective disagrees with lax at {kib} KiB: {errs}")

    wire = PRECISIONS["int8"]
    kib = 16384
    size = kib * 1024 // 4
    x = jax.random.normal(jax.random.PRNGKey(7), (n, size), jnp.float32)
    ar8 = guard_schedule(
        svc.get_axis_executable("x", n, size).schedule.with_wire(wire))
    err = relerr(run(lambda v: ar8.allreduce(v, "x"), x),
                 run(lambda v: lax.psum(v, "x"), x))
    log(f"collectives int8-wire allreduce {kib} KiB/device: rel err "
        f"{err:.3e} (budget {wire.error_budget:g})")
    check(err < wire.error_budget, "int8-wire allreduce exceeds its budget")


def check_state_sharding(state, n: int) -> None:
    """Every ZeRO-3 shard row lives on its own chip."""
    import jax
    for leaf in jax.tree.leaves(state):
        if leaf.ndim == 0:
            continue
        devs = {sh.device for sh in leaf.addressable_shards}
        check(leaf.shape[0] == n and len(devs) == n,
              f"state leaf {leaf.shape} spans {len(devs)} devices, not {n}")


def four_chips(n: int) -> None:
    from repro.runtime.metrics import default_metrics

    with phase("collectives"):
        check_collectives(n)
    check_no_fallback()

    reg = default_metrics()
    before = reg.snapshot()
    with phase("trainer sync=plan"):
        plan = train(FOUR_CHIP_LAYERS, "plan", global_batch=n)
    snap = reg.snapshot()

    def delta(name):
        return (snap.get(name, {}).get("value", 0.0)
                - before.get(name, {}).get("value", 0.0))

    bucketed, per_leaf = (delta("zero3_bucketed_traces_total"),
                          delta("zero3_per_leaf_traces_total"))
    log(f"zero3 sync path with --sync plan: {bucketed:g} bucketed, "
        f"{per_leaf:g} per-leaf traces")
    check(bucketed > 0 and per_leaf == 0,
          "the plan run did not take the bucketed ZeRO-3 path")
    check_state_sharding(plan["state"], n)
    plan_losses = plan["losses"]
    del plan
    with phase("trainer sync=psum"):
        psum = train(FOUR_CHIP_LAYERS, "psum", global_batch=n)
    check_state_sharding(psum["state"], n)
    diffs = [abs(a - b) for a, b in zip(plan_losses, psum["losses"])]
    log(f"plan vs psum loss diffs {diffs} (tol {LOSS_TOL:g})")
    check(max(diffs) < LOSS_TOL, "plan and psum losses disagree")
    check_no_fallback()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip collectives and ZeRO-3 "
                         "plan-vs-psum trainer")
    args = ap.parse_args()
    want = 4 if args.four_chips else 1

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: FAIL no repro package under {SRC}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL no TPU (JAX's first device is "
              f"{dev.platform})", file=sys.stderr)
        return 1
    if len(devs) < want:
        print(f"chip_smoke: FAIL {want} chips needed, {len(devs)} found",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    log(f"device: {dev.device_kind} x{len(devs)} ({dev.platform}); "
        f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(want)
        else:
            one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL {e}", file=sys.stderr)
        return 1
    log(f"compile {clock.seconds:.1f} s (persistent cache: {clock.hits} "
        f"hits, {clock.misses} misses); wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
