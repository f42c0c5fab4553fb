"""End-to-end driver: train a ~100M-parameter dense LM for a few hundred
steps on the local devices, with the paper's technique as the gradient-sync
strategy (manual ZeRO-3 engine + GenModel-selected collectives), async
checkpointing, fault-tolerant loop, and straggler watchdog.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import os
# default: single local device (fastest on a 1-core container); set
# XLA_FLAGS=--xla_force_host_platform_device_count=4 to exercise DP.

import argparse
import dataclasses

import jax

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.train import TrainConfig, run_training
from repro.models.config import ModelConfig


def cfg_100m() -> ModelConfig:
    """~100M dense LM (GPT-2-medium-ish) in the stablelm family."""
    base = get_config("stablelm-12b")
    return dataclasses.replace(
        base, name="lm-100m", n_layers=8, d_model=768, n_heads=12,
        n_kv_heads=4, d_head=64, d_ff=2048, vocab=8192)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--engine", default="auto",
                    choices=["manual", "auto"],
                    help="auto = pjit/XLA collectives; manual = ZeRO-3 "
                    "shard_map with GenModel-selected plans (slower on "
                    "CPU, the paper's technique end-to-end)")
    ap.add_argument("--sync", default="gentree")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    # register the 100M config under a temp name by monkey-loading
    import repro.configs as C
    import types
    mod = types.ModuleType("repro.configs.lm_100m")
    mod.CONFIG = cfg_100m()
    mod.SUPPORTED_SHAPES = ("train_4k",)
    import sys
    sys.modules["repro.configs.lm_100m"] = mod

    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    n = mod.CONFIG.params_count()
    print(f"training lm-100m ({n/1e6:.0f}M params) on "
          f"{len(jax.devices())} devices, engine={args.engine}, "
          f"sync={args.sync}")
    out = run_training(
        TrainConfig(arch="lm-100m", layers=mod.CONFIG.n_layers,
                    steps=args.steps, seq_len=128,
                    global_batch=max(2, len(jax.devices())),
                    engine=args.engine, sync=args.sync,
                    lr=6e-4, ckpt_dir=args.ckpt_dir, ckpt_every=100,
                    log_every=20),
        mesh=mesh)
    losses = out["losses"]
    print(f"loss: {losses[0]:.3f} → {losses[-1]:.3f} over "
          f"{len(losses)} steps")
    assert losses[-1] < losses[0], "loss must decrease"


if __name__ == "__main__":
    main()
