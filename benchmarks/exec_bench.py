"""Executed-plan step time vs `lax.psum` (DESIGN.md §8).

For the first time the repo can measure what it *runs*, not only what it
*prices*: lowered GenTree plans and lowered flat builders execute under
shard_map on an 8-device host-CPU mesh next to XLA's native psum, and the
per-step wall-clock lands in BENCH_core.json so the executed-plan
trajectory is tracked across PRs.

Numbers here are host-CPU ppermute emulation — psum is expected to win on
this substrate (XLA fuses the whole reduction); the benchmark's gates are
correctness (every executed schedule matches psum) and the recorded
trend, not a speed win. Run standalone with

    PYTHONPATH=src python -m benchmarks.exec_bench [--json PATH]

or as part of `benchmarks.run --only exec`. The measurement runs in a
subprocess so the 8-device XLA flag does not leak into sibling benchmarks.
The subprocess runs with JAX_PLATFORMS=cpu: it emulates its mesh on host
CPU devices by design, and on a machine with a TPU the parent, which has
imported JAX, holds the chip.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import fmt_table

_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core import plans, topology
from repro.core.gentree import gentree
from repro.core.lower import lower_plan

N, SIZE = 8, 1 << 16
mesh = make_mesh((N,), ("x",))
x = jax.random.normal(jax.random.PRNGKey(0), (N, SIZE), jnp.float32)


def bench(fn):
    f = jax.jit(shard_map(lambda v: fn(v[0])[None], mesh=mesh,
                          in_specs=P("x"), out_specs=P("x")))
    out = f(x)
    jax.block_until_ready(out)          # compile + warm
    reps, times = 5, []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(x))
        times.append(time.perf_counter() - t0)
    return np.asarray(f(x))[0], sorted(times)[reps // 2]


want, psum_s = bench(lambda v: jax.lax.psum(v, "x"))
rows = {"psum": {"ms": psum_s * 1e3, "vs_psum": 1.0, "ok": True}}

CASES = {
    "exec_gentree_ss8": gentree(topology.single_switch(N), float(SIZE)).plan,
    "exec_gentree_sym2x4": gentree(topology.symmetric_tree(2, 4),
                                   float(SIZE)).plan,
    "exec_ring": plans.ring(N, float(SIZE)),
    "exec_cps": plans.cps(N, float(SIZE)),
}
for name, plan in CASES.items():
    cs = lower_plan(plan)
    got, dt = bench(lambda v, cs=cs: cs.allreduce(v, "x"))
    ok = bool(np.allclose(got, want, rtol=1e-5, atol=1e-5))
    rows[name] = {"ms": dt * 1e3, "vs_psum": dt / psum_s, "ok": ok,
                  "rounds": cs.total_rounds()}
print("RESULTS " + json.dumps(rows))
"""


def run() -> dict:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep * bool(env.get("PYTHONPATH")) \
        + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _DRIVER], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"exec bench driver failed: {out.stderr[-2000:]}")
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULTS ")][-1]
    rows = json.loads(line[len("RESULTS "):])

    table = [{"schedule": k, "step ms": f"{v['ms']:.2f}",
              "vs psum": f"{v['vs_psum']:.1f}x",
              "rounds": v.get("rounds", "-"),
              "correct": "yes" if v["ok"] else "NO"}
             for k, v in rows.items()]
    print(fmt_table(table, ["schedule", "step ms", "vs psum", "rounds",
                            "correct"],
                    "executed plan step time vs lax.psum (8 host devices)"))

    all_ok = all(v["ok"] for v in rows.values())
    if not all_ok:
        raise AssertionError(f"executed schedule diverged from psum: {rows}")
    # scalar metrics ride into BENCH_core.json via benchmarks.run
    flat = {"ok": all_ok, "psum_ms": round(rows["psum"]["ms"], 3)}
    for k, v in rows.items():
        if k != "psum":
            flat[f"{k}_ms"] = round(v["ms"], 3)
            flat[f"{k}_vs_psum"] = round(v["vs_psum"], 2)
    return flat


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args()
    out = run()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
