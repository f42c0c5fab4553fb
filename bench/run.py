#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for `--seconds`, checks the result against the
configuration's plain reference, and prints one JSON line as the last
line of standard output:

  {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
   "checks"}

With `--trace 0` the metrics are the cell's end-to-end metrics of
BENCHMARK.json; with `--trace 1` its per-layer metrics, each read by
`bench/metrics/<name>.py` from the run's record and profiler trace. The
numbers compared and their limits are the last lines on standard error
and the last key of the JSON line.

Exits non-zero with no result where JAX's first device is not a TPU,
where there are fewer chips than the cell asks for, or where the device
is not in bench/peaks.json.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def metrics_of(c: dict, out: dict, trace: bool) -> dict:
    rec = out["record"]
    if not trace:
        return {m["name"]: {"value": rec[m["name"]], "unit": m["unit"]}
                for m in c["end_to_end"]}
    got = {}
    for m in c["per_layer"]:
        v = harness.metric_reader(m["name"])(rec)
        if v is not None:
            got[m["name"]] = {"value": v, "unit": m["unit"]}
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        c = harness.cell(args.workload)
        harness.use_caches()
        driver = harness.load_module(c["driver"])
        out = driver.run(c, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START)
    except harness.BenchError as e:
        harness.log(f"bench: FAIL {e}")
        return 1
    rec = out["record"]
    device = out["device"]
    breakdown = None
    if args.trace:
        tr = rec.get("trace") or {}
        device["busy_s"] = tr.get("busy_s", 0.0)
        device["window_s"] = tr.get("window_s", 0.0)
        if tr:
            breakdown = {"device_ops": tr["device_ops"],
                         "idle_gaps": tr["idle_gaps"]}
    metrics = metrics_of(c, out, bool(args.trace))
    harness.log(f"window: {rec['steps']} steps in {rec['window_s']:.3f} s, "
                f"{rec['tokens_per_s']:.1f} tokens/s, set-up "
                f"{rec['setup_s']:.1f} s, failed {out['failed']}")
    for name, ch in out["checks"].items():
        harness.log(f"check {name}: {ch['value']!r} limit {ch['limit']!r}")
    print(harness.result_line(out["correct"], out["attempted"],
                              out["failed"], metrics, device, out["checks"],
                              breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
