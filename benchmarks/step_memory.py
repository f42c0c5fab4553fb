"""Device memory and compile time of one training step, compiled for a
described TPU v5e without a chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m benchmarks.step_memory \
        --layers 10 [--chips 4 --sync plan] [--seq-len 4096]

Builds the ZeRO-3 step of `launch.train.make_manual_train_step` for the
config at its published widths with `--layers` layers, over a mesh of
`--chips` described v5e devices, with the state and batch as sharded
shapes (nothing is allocated), and prints XLA's `memory_analysis()` per
device and the host seconds the TPU compiler took. This is how the depth
cuts of `chip_smoke.py` are chosen; the chip run then prints the same
analysis of the step it compiled. Nothing here runs: no time here is a
device time.
"""
from __future__ import annotations

import argparse
import math
import os
import time
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

GIB = 1 << 30


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="rwkv6-1.6b")
    ap.add_argument("--layers", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--sync", default="plan")
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=None,
                    help="global batch (default: one sequence per chip)")
    args = ap.parse_args()

    from jax.experimental import topologies

    from repro.configs import get_config
    from repro.core.sync import SyncConfig
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_manual_train_step
    from repro.models.registry import build
    from repro.optim import adamw_init

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    n = args.chips
    mesh = make_mesh((n, 1), ("data", "model"), devices=topo.devices[:n])
    rows = NamedSharding(mesh, P("data", None))

    import dataclasses
    cfg = dataclasses.replace(get_config(args.arch), n_layers=args.layers)
    api = build(cfg)

    def shard(leaf):          # shard_params_zero3's (n, chunk) layout
        return jax.ShapeDtypeStruct((n, -(-math.prod(leaf.shape) // n)),
                                    leaf.dtype, sharding=rows)

    params = jax.tree.map(shard, api.params_spec())
    opt = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=rows if x.ndim else NamedSharding(mesh, P())),
        jax.eval_shape(adamw_init, params))
    data = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq_len,
        global_batch=args.batch or n, seed=0,
        embed_dim=cfg.d_model if cfg.embeds_input else 0))
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rows),
        data.batch_at(0))

    step = make_manual_train_step(api, mesh,
                                  sync=SyncConfig(strategy=args.sync))
    # the model picks its Pallas kernels by the default backend, which is
    # the CPU here; the compile target is the described TPU
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        t0 = time.perf_counter()
        lowered = step.lower({"params": params, "opt": opt}, batch)
        t1 = time.perf_counter()
        mem = lowered.compile().memory_analysis()
        t2 = time.perf_counter()
    live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"{args.arch} layers={args.layers}/{get_config(args.arch).n_layers}"
          f" chips={n} sync={args.sync} seq={args.seq_len}: per device "
          f"args {mem.argument_size_in_bytes / GIB:.2f} GiB, temps "
          f"{mem.temp_size_in_bytes / GIB:.2f} GiB, outputs "
          f"{mem.output_size_in_bytes / GIB:.2f} GiB, aliased "
          f"{mem.alias_size_in_bytes / GIB:.2f} GiB; live {live / GIB:.2f} "
          f"GiB; host lower {t1 - t0:.1f} s, "
          f"compile {t2 - t1:.1f} s")


if __name__ == "__main__":
    main()
