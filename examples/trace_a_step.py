"""Trace one bucketed, double-buffered sync step (DESIGN.md §9 + §11).

Enables the process-wide span tracer, runs a gradient sync with
`SyncConfig(strategy="plan")` — the GenTree plan lowered to a compiled
schedule, partitioned into GenModel-sized buckets with bucket k's
AllGather overlapping bucket k+1's ReduceScatter — on an 8-host-device
mesh, and exports a Chrome-trace JSON you can load in chrome://tracing
or https://ui.perfetto.dev.

The names inside the shard_map body (`sync/bucketed`, per-bucket
`bucket/rs<i>` / `bucket/ag<i>`, per-step and per-round `exec/...`) are
scopes: they name the device ops of the compiled step (its `op_name`
metadata, which a profiler trace carries), nested exactly as the schedule
executes, and cost nothing when it runs. The host spans
(`example/sync_step`, `planner/generate_plan`, `planner/bucket_sweep`)
go to the ring and the Chrome trace, and appear on the JAX profiler's
clock whenever a profiler session records (DESIGN.md §11).

Run:  PYTHONPATH=src python examples/trace_a_step.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core.sync import SyncConfig, sync_gradients
from repro.runtime.metrics import default_metrics
from repro.runtime.trace import default_tracer

TRACE_PATH = "trace_a_step.json"
METRICS_PATH = "trace_a_step_metrics.json"


def main():
    tracer = default_tracer()
    tracer.enabled = True

    n = 8
    axes = [("data", n)]
    mesh = make_mesh((n,), ("data",))
    # bucket_bytes pinned below the pytree size so the step really runs
    # multiple buckets and the RS(k+1)/AG(k) overlap shows in the trace
    cfg = SyncConfig(strategy="plan", bucket_bytes=8192, pipeline=True)

    # a small mixed pytree of "gradients", replicated rows per device
    key = jax.random.PRNGKey(0)
    grads = {}
    for i, size in enumerate((4096, 1536, 257, 64)):
        key, sub = jax.random.split(key)
        grads[f"leaf{i}"] = jax.random.normal(sub, (n, size), jnp.float32)

    stats = {}
    f = shard_map(
        lambda g: jax.tree.map(
            lambda v: v[None],
            sync_gradients(jax.tree.map(lambda v: v[0], g), axes, cfg,
                           stats=stats)),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    with tracer.span("example/sync_step", leaves=len(grads)):
        got = jax.jit(f)(grads)

    # correctness: the traced step is still the psum answer
    for k, v in grads.items():
        want = np.asarray(v.sum(0))
        err = np.abs(np.asarray(got[k])[0] - want).max() / \
            (np.abs(want).max() + 1e-30)
        assert err < 1e-5, (k, err)
    assert stats.get("num_buckets", 0) >= 2, "expected a multi-bucket step"
    print(f"bucketed sync == psum  (buckets={stats.get('num_buckets')}, "
          f"predicted pipelined {stats.get('predicted_pipelined'):.2e} s "
          f"vs serial {stats.get('predicted_serial'):.2e} s)")

    tracer.export_chrome(TRACE_PATH)
    default_metrics().export(METRICS_PATH)

    # prove the artifact is loadable and holds the host span; the step's
    # scopes nest as the schedule executes: sync -> bucket halves -> rounds
    with open(TRACE_PATH) as fh:
        doc = json.load(fh)
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in events}
    assert "example/sync_step" in names, "missing span 'example/sync_step'"
    lowered = jax.jit(f).lower(grads).as_text(debug_info=True)
    scopes = sorted(set(re.findall(
        r"(?:sync|bucket|exec)/[a-z_]+\d*(?:/step\d+)?", lowered)))
    for expected in ("sync/bucketed", "bucket/rs_ag0", "exec/round0"):
        assert expected in scopes, f"missing scope {expected!r}"
    print(f"wrote {TRACE_PATH}: {len(events)} host spans "
          f"({len(names)} distinct), e.g. " + ", ".join(sorted(names)[:6]))
    print(f"device scopes of the step ({len(scopes)}), e.g. "
          + ", ".join(scopes[:8]))
    print(f"wrote {METRICS_PATH} (+ .prom): "
          f"{len(default_metrics().snapshot())} metrics")
    print("load the trace in chrome://tracing or ui.perfetto.dev")


if __name__ == "__main__":
    main()
