#!/usr/bin/env python3
"""Device memory and host compile time of a cell's training step,
compiled for a described TPU v5e without a chip.

    JAX_PLATFORMS=cpu python3 bench/size_step.py --workload <cell> [--layers L ...]

Builds the cell's step exactly as bench/drivers/train.py does (the
program's `make_manual_train_step` over the cell's chips, state and batch
as sharded shapes; nothing is allocated), at each `--layers` depth of the
cell's configuration, and prints XLA's `memory_analysis()` per device and
the host seconds the TPU compiler took. A copy of
benchmarks/step_memory.py that reads the cell's files. Nothing runs: no
number here is a device time.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from pathlib import Path
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import harness  # noqa: E402

GIB = 1 << 30


def size(c: dict, layers: int, topo) -> dict:
    from repro.core.sync import SyncConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_manual_train_step
    from repro.models.registry import build
    from repro.optim import AdamWConfig, adamw_init

    from drivers.train import model_config
    n = c["entry"]["chips"]
    wl, tr = c["workload"], c["workload"]["traffic"]
    config = dict(c["config"], num_hidden_layers=layers)
    mesh = make_mesh((n, 1), ("data", "model"), devices=topo.devices[:n])
    rows = NamedSharding(mesh, P("data", None))
    api = build(model_config(config))

    def shard(leaf):          # shard_params_zero3's (n, chunk) layout
        return jax.ShapeDtypeStruct((n, -(-math.prod(leaf.shape) // n)),
                                    leaf.dtype, sharding=rows)

    params = jax.tree.map(shard, api.params_spec())
    opt = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=rows if x.ndim else NamedSharding(mesh, P())),
        jax.eval_shape(adamw_init, params))
    B = n * tr["sequences_per_chip"]
    sds = jax.ShapeDtypeStruct((B, tr["seq_len"]), jax.numpy.int32,
                               sharding=rows)
    batch = {"tokens": sds, "labels": sds}
    step = make_manual_train_step(api, mesh, AdamWConfig(**wl["optimizer"]),
                                  sync=SyncConfig(strategy=wl["sync"]))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        t0 = time.perf_counter()
        lowered = step.lower({"params": params, "opt": opt}, batch)
        t1 = time.perf_counter()
        mem = lowered.compile().memory_analysis()
        t2 = time.perf_counter()
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    n_params = sum(math.prod(x.shape) for x in
                   jax.tree.leaves(api.params_spec()))
    return {"layers": layers, "params": n_params,
            "live_gib_per_chip": round(live / GIB, 3),
            "temps_gib": round(mem.temp_size_in_bytes / GIB, 3),
            "host_lower_s": round(t1 - t0, 1),
            "host_compile_s": round(t2 - t1, 1)}


def size_reference(c: dict, topo) -> dict:
    """The reference's loss-and-gradient program at the cell's size, its
    state split over the cell's described chips as the oracle splits it."""
    import numpy as np
    from jax.sharding import Mesh

    import oracle
    n = c["entry"]["chips"]
    tr = c["workload"]["traffic"]
    mesh = Mesh(np.array(topo.devices[:n]), ("d",))
    ref = harness.load_module(c["reference"])
    _, grads, _, _ = oracle.steps_of(ref, c["config"], c["workload"]["optimizer"],
                                     oracle.F32, mesh)
    shapes = jax.eval_shape(lambda: ref.init_params(jax.random.PRNGKey(0),
                                                    c["config"]))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, jax.numpy.float32,
        sharding=oracle.leaf_sharding(s.shape, mesh)), shapes)
    B = n * tr["sequences_per_chip"]
    rows = NamedSharding(mesh, P("d") if B % n == 0 else P())
    tok = jax.ShapeDtypeStruct((B, tr["seq_len"]), jax.numpy.int32,
                               sharding=rows)
    mask = jax.ShapeDtypeStruct((B, tr["seq_len"]), jax.numpy.float32,
                                sharding=rows)
    t0 = time.perf_counter()
    mem = grads.lower(params, tok, tok, mask).compile().memory_analysis()
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    return {"reference": True, "live_gib_per_chip": round(live / GIB, 3),
            "temps_gib": round(mem.temp_size_in_bytes / GIB, 3),
            "host_compile_s": round(time.perf_counter() - t0, 1)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, nargs="*", default=[])
    ap.add_argument("--reference", action="store_true",
                    help="also compile the reference's gradient program")
    args = ap.parse_args()
    from jax.experimental import topologies
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    c = harness.cell(args.workload)
    for L in args.layers:
        try:
            print(json.dumps(dict(size(c, L, topo), workload=args.workload)),
                  flush=True)
        except Exception as e:          # the compiler's refusal is the answer
            print(json.dumps({"workload": args.workload, "layers": L,
                              "refused": repr(e)[:400]}), flush=True)
    if args.reference:
        print(json.dumps(dict(size_reference(c, topo),
                              workload=args.workload)), flush=True)


if __name__ == "__main__":
    main()
