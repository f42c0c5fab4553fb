#!/usr/bin/env python3
"""The readings that a cell's limits are set from, many seeds in one
process (the step compiles once).

    python3 bench/calibrate.py --workload <cell> --seeds N [N ...] \
        [--control-seeds N [N ...]]

For every seed: the program's first steps, exactly as a run of
bench/run.py takes them, against the reference (the lower readings).
For every control seed besides: the control (the reference with float8
products, bench/oracle.py: FP8) and the reference with half of the batch
left out, each put in the program's place against the reference (the
upper readings). One JSON line per seed and kind on standard output.
Needs the chips the cell asks for; the benchmark's own runs do not run
this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    c = harness.cell(args.workload)
    harness.use_caches()
    driver = harness.load_module(c["driver"])
    import oracle
    tn = driver.Trainer(c, harness.chips(c["entry"]["chips"]))

    def emit(kind, seed, numbers, extra=None):
        print(json.dumps({"workload": args.workload, "kind": kind,
                          "seed": seed, **numbers, **(extra or {})}),
              flush=True)

    for seed in args.seeds:
        t0 = time.perf_counter()
        tn.start(seed)
        got, failed = tn.first_steps()
        mismatch = tn.input_mismatch()
        tn.free()
        want = tn.reference()
        emit("program", seed, oracle.gaps(got, want),
             {"input_mismatch": mismatch, "failed": failed,
              "losses": got["losses"], "ref_losses": want["losses"],
              "seconds": round(time.perf_counter() - t0, 1)})
        if seed in args.control_seeds:
            for kind, kw in (("control_fp8", {"mm": oracle.FP8}),
                             ("fault_half_batch", {"half_batch": True})):
                emit(kind, seed, oracle.gaps(tn.reference(**kw), want))
    return 0


if __name__ == "__main__":
    sys.exit(main())
