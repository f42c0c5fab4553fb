"""Telemetry hot-path overhead + online-refit convergence (DESIGN.md §10).

Two gates keep the closed loop honest:

  * **observe() overhead < 1% of a simulated step** — feeding a measured
    collective into the loop (ring add + residual update + CPS-equivalent
    sample + drift check) must be noise next to the step it instruments.
    The "simulated step" is the repo's own smoke training step
    (`launch.train.run_training`, manual engine, sync="plan"): the bench
    reads the median per-step wall time straight from the `train/step`
    telemetry ring the trainer feeds — the same datapath the watchdog
    reads — so the gate prices observe() against exactly the step it
    would instrument in production.
  * **refit convergence within 10%** — the synthetic drift scenario (the
    acceptance criterion of PR 5): a service mis-seeded 3× low on α and
    6× low on β observes ground-truth measurements, refits from
    telemetry, and afterwards every observed (n, S) point must price
    within 10% of measured.
  * **tracer overhead < 2% of a smoke train step** — the same smoke run
    executed twice, once with the span tracer disabled and once enabled
    (fresh telemetry hub each, so the two medians are clean); the traced
    run's median per-step wall time may exceed the untraced one by at
    most 2%. The traced run's Chrome trace and metrics snapshot are
    exported as `BENCH_trace.json` / `BENCH_metrics.json` so CI uploads
    a loadable trace artifact alongside the numbers.

`benchmarks.run --json` records `telemetry_overhead_pct`,
`trace_overhead_pct` and `refit_residual_ratio` in BENCH_core.json so the
trajectory is tracked across PRs. Runs headless on CPU (the smoke train
step jits on the local device; no multi-device mesh needed).

    PYTHONPATH=src python -m benchmarks.telemetry_bench [--json PATH]
"""
from __future__ import annotations

import dataclasses
import time

from repro.core.cost_model import PAPER_TABLE5
from repro.core.simulator import Simulator
from repro.core.sync import level_switch_topo
from repro.planner.service import PlannerService, RefitPolicy

from .common import fmt_table

OBSERVE_CALLS = 2000
SIM_STEPS = 50
SIZES = [(8, 1e6), (8, 4e6), (4, 1e6), (8, 1.6e7), (4, 4e6),
         (8, 2e6), (8, 8e6), (4, 2e6)]


def _mis_seeded_service(policy: RefitPolicy) -> PlannerService:
    true = PAPER_TABLE5
    wrong = dict(true)
    wrong["root_sw"] = dataclasses.replace(
        true["root_sw"], alpha=true["root_sw"].alpha / 3,
        beta=true["root_sw"].beta / 6)
    return PlannerService(params=wrong, refit_policy=policy)


def _measure(svc, n, size):
    """Ground truth: the chosen plan simulated under the TRUE params."""
    resp = svc.get_axis_executable("data", n, size, level="root_sw")
    topo = level_switch_topo(n, PAPER_TABLE5, "root_sw")
    meas = Simulator(topo, PAPER_TABLE5,
                     unit_bytes=4).simulate(resp.plan).total
    return resp, meas


def run() -> dict:
    out: dict = {"ok": True}

    # ---- gate 1: observe() hot-path overhead ------------------------------
    svc = _mis_seeded_service(RefitPolicy(enabled=False))
    resp, meas = _measure(svc, 8, 4e6)

    # the simulated step the overhead is charged against: the repo's own
    # smoke training step, whose per-step wall times land in the
    # train/step telemetry ring (the watchdog datapath) as run_training
    # executes
    from repro.launch.train import TrainConfig, run_training
    from repro.runtime.telemetry import default_telemetry
    run_training(TrainConfig(arch="stablelm-12b", steps=SIM_STEPS,
                             seq_len=32, global_batch=4, engine="manual",
                             sync="plan", log_every=10 ** 6), on_log=lambda *a, **k: None)
    ring = default_telemetry().ring("train/step")
    assert ring.count >= SIM_STEPS, "trainer did not feed the step ring"
    step_s = ring.percentile(50.0)               # median: jit-proof

    # BOTH observe branches, warmed first: explicit predicted (the e2e
    # closed-loop scenario) AND default pricing (what the production
    # wiring — train's sync probe, serve's decode observe — actually
    # calls; its exact-size halves pricing is memoized per params
    # version, so the steady state is what the gate bounds)
    svc.observe("root_sw", 8, 4e6, meas, predicted=resp.predicted_time,
                key=resp.key)                    # warm create-on-demand
    t0 = time.perf_counter()
    for _ in range(OBSERVE_CALLS):
        svc.observe("root_sw", 8, 4e6, meas,
                    predicted=resp.predicted_time, key=resp.key)
    observe_s = (time.perf_counter() - t0) / OBSERVE_CALLS

    svc.observe("root_sw", 8, 4e6, meas, key=resp.key)   # warm pricing
    t0 = time.perf_counter()
    for _ in range(OBSERVE_CALLS):
        svc.observe("root_sw", 8, 4e6, meas, key=resp.key)
    observe_def_s = (time.perf_counter() - t0) / OBSERVE_CALLS

    overhead_pct = 100.0 * max(observe_s, observe_def_s) / step_s
    rows = [{"metric": "simulated train step (median)",
             "value": f"{step_s * 1e6:.1f} us"},
            {"metric": "observe() call (explicit predicted)",
             "value": f"{observe_s * 1e6:.1f} us"},
            {"metric": "observe() call (default pricing)",
             "value": f"{observe_def_s * 1e6:.1f} us"},
            {"metric": "overhead (worst branch)",
             "value": f"{overhead_pct:.3f} %"}]
    assert overhead_pct < 1.0, (
        f"observe() overhead {overhead_pct:.2f}% of a simulated step "
        f"(gate: < 1%)")

    # ---- gate 2: refit convergence on the synthetic drift scenario --------
    svc = _mis_seeded_service(RefitPolicy(min_samples=6,
                                          drift_threshold=0.15, cooldown=6))
    refits = 0
    for n, size in SIZES * 3:
        resp, meas = _measure(svc, n, size)
        obs = svc.observe("root_sw", n, size, meas,
                          predicted=resp.predicted_time, key=resp.key)
        refits += int(obs["refit"])
    assert refits >= 1, "synthetic drift scenario never triggered a refit"

    worst = 0.0
    for n, size in SIZES:
        resp, meas = _measure(svc, n, size)
        worst = max(worst, abs(resp.predicted_time - meas) / meas)
    rows.append({"metric": "refits fired", "value": str(refits)})
    rows.append({"metric": "worst post-refit residual",
                 "value": f"{worst * 100:.2f} %"})
    assert worst < 0.10, (
        f"post-refit predicted cost diverges {worst * 100:.1f}% from "
        f"measured (gate: < 10%)")

    # ---- gate 3: span-tracer overhead on the smoke train step -------------
    # Same smoke config twice — untraced then traced — each against a
    # FRESH telemetry hub so the two train/step medians don't mix with
    # each other or with earlier benches in the same process. The traced
    # run's spans + metrics are exported for the CI artifact upload.
    from repro.runtime.telemetry import (Telemetry, peek_default_telemetry,
                                         set_default_telemetry)
    from repro.runtime.trace import Tracer, set_default_tracer
    from repro.runtime.metrics import default_metrics

    tcfg = TrainConfig(arch="stablelm-12b", steps=SIM_STEPS,
                       seq_len=32, global_batch=4, engine="manual",
                       sync="plan", log_every=10 ** 6)
    old_tele = peek_default_telemetry()
    old_tracer = set_default_tracer(Tracer(enabled=False))
    try:
        set_default_telemetry(Telemetry())
        run_training(tcfg, on_log=lambda *a, **k: None)
        untraced_s = default_telemetry().ring("train/step").percentile(50.0)

        traced_tracer = Tracer(enabled=True)
        set_default_tracer(traced_tracer)
        set_default_telemetry(Telemetry())
        run_training(tcfg, on_log=lambda *a, **k: None)
        traced_s = default_telemetry().ring("train/step").percentile(50.0)

        traced_tracer.export_chrome("BENCH_trace.json")
        default_metrics().export("BENCH_metrics.json")
    finally:
        set_default_tracer(old_tracer)
        set_default_telemetry(old_tele)

    trace_overhead_pct = max(
        0.0, 100.0 * (traced_s - untraced_s) / untraced_s)
    rows.append({"metric": "smoke step untraced (median)",
                 "value": f"{untraced_s * 1e6:.1f} us"})
    rows.append({"metric": "smoke step traced (median)",
                 "value": f"{traced_s * 1e6:.1f} us"})
    rows.append({"metric": "tracer overhead",
                 "value": f"{trace_overhead_pct:.3f} %"})
    rows.append({"metric": "spans recorded (traced run)",
                 "value": str(len(traced_tracer.spans))})
    assert traced_tracer.spans, "traced smoke run recorded no spans"
    assert trace_overhead_pct < 2.0, (
        f"span tracer costs {trace_overhead_pct:.2f}% of a smoke train "
        f"step (gate: < 2%)")

    print(fmt_table(rows, ["metric", "value"],
                    "telemetry hot path + online refit convergence"))
    out["telemetry_overhead_pct"] = round(overhead_pct, 4)
    out["trace_overhead_pct"] = round(trace_overhead_pct, 4)
    out["refit_residual_ratio"] = round(worst, 4)
    out["refits"] = refits
    out["trace_spans"] = len(traced_tracer.spans)
    return out


def main() -> None:
    import argparse
    import json
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args()
    res = run()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
