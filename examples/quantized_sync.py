"""One bucketed fp8 gradient sync, priced per GenModel term (DESIGN.md §13).

Runs a gradient sync with `SyncConfig(strategy="plan", precision="fp8",
tolerance=0.3)` on an 8-host-device mesh: the bucket-plan sweep argmins
jointly over bucket size AND wire precision, the chosen schedule moves
fp8 payloads + per-tile f32 scales through the coalesced ppermute rounds,
and the folds run the fused dequant-accumulate kernel. The measured step
is fed back through `PlannerService.observe(precision="fp8")`, so the
cost ledger decomposes the quoted prediction into per-term seconds with
the quant passes charged to γ/δ and the shrunk wire to β/incast — then
prints that ledger next to the full-precision pricing of the same sync.

Run:  PYTHONPATH=src python examples/quantized_sync.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core.cost_model import PRECISIONS
from repro.core.sync import SyncConfig, sync_gradients
from repro.planner.service import default_service


def main():
    n = 8
    axes = [("data", n)]
    mesh = make_mesh((n,), ("data",))
    cfg = SyncConfig(strategy="plan", precision="fp8", tolerance=0.3)

    key = jax.random.PRNGKey(0)
    grads = {}
    for i, size in enumerate((65536, 16384, 4096, 257)):
        key, sub = jax.random.split(key)
        grads[f"leaf{i}"] = jax.random.normal(sub, (n, size), jnp.float32)
    total = float(sum(v[0].size for v in grads.values()))

    stats = {}
    f = shard_map(
        lambda g: jax.tree.map(
            lambda v: v[None],
            sync_gradients(jax.tree.map(lambda v: v[0], g), axes, cfg,
                           stats=stats)),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    step = jax.jit(f)
    got = step(grads)                       # compile + trace
    t0 = time.perf_counter()
    got = jax.block_until_ready(step(grads))
    measured = time.perf_counter() - t0

    # correctness against psum, within the fp8 error budget
    budget = PRECISIONS["fp8"].error_budget
    worst = 0.0
    for k, v in grads.items():
        want = np.asarray(v.sum(0), np.float64)
        err = np.abs(np.asarray(got[k], np.float64)[0] - want).max() / \
            (np.abs(want).max() + 1e-30)
        worst = max(worst, err)
        assert err < budget, (k, err)
    print(f"fp8 bucketed sync == psum within budget "
          f"(worst rel err {worst:.4f} < {budget}), "
          f"precision={stats.get('precision')}, "
          f"buckets={stats.get('num_buckets')}, measured {measured:.4f} s")

    # ---- the cost ledger, per term (DESIGN.md §11 + §13) -------------------
    svc = default_service()
    svc.observe("root_sw", n, total, measured, precision="fp8",
                dtype="float32")
    entry = svc.telemetry.ledger.entries("root_sw")[-1]
    full = svc._axis_halves_time(n, "root_sw", total, "float32",
                                 svc._effective_axis_params())
    print(f"\nquoted prediction {entry.predicted:.3e} s "
          f"(f32 pricing of the same sync: {sum(full):.3e} s) "
          f"vs measured {entry.measured:.3e} s")
    print(f"{'term':>8s}  {'seconds':>12s}  {'share':>7s}")
    tot = sum(entry.shares.values()) or 1.0
    for term, sec in sorted(entry.shares.items(), key=lambda kv: -kv[1]):
        print(f"{term:>8s}  {sec:12.3e}  {sec / tot * 100:6.1f}%")
    print("\nthe quant passes ride γ (adds) and δ (memory ops); β and the"
          "\nincast term price the compressed wire — the trade the sweep"
          "\nargmins over (DESIGN.md §13).")


if __name__ == "__main__":
    main()
