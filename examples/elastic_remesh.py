"""Elastic scaling demo: train on an 8-device mesh, checkpoint, lose half
the fleet, restore the SAME checkpoint onto a 4-device mesh, and keep
training with identical semantics (the data pipeline is pure in the step
index, so the loss sequence continues exactly).

Run:  PYTHONPATH=src python examples/elastic_remesh.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import tempfile

import jax

from repro.launch.mesh import make_mesh
from repro.launch.train import TrainConfig, run_training


def main():
    ckpt = tempfile.mkdtemp(prefix="elastic_")
    devs = jax.devices()
    print(f"{len(devs)} devices available")

    mesh8 = make_mesh((8, 1), ("data", "model"))
    tc = dict(arch="rwkv6-1.6b", seq_len=64, global_batch=8, lr=1e-3,
              ckpt_dir=ckpt, ckpt_every=10, log_every=5)
    print("— phase 1: 8-device mesh, steps 0–19 —")
    out1 = run_training(TrainConfig(**tc, steps=20), mesh=mesh8)

    # "lose a pod": continue on half the devices. The checkpoint is
    # device-agnostic (numpy), so restore just re-shards onto the new
    # mesh (runtime.elastic_remesh under the hood of the restore path).
    mesh4 = make_mesh((4, 1), ("data", "model"))
    print("— phase 2: restored onto a 4-device mesh, steps 20–39 —")
    out2 = run_training(TrainConfig(**tc, steps=40), mesh=mesh4)

    print(f"loss at handover: {out1['losses'][-1]:.4f} → "
          f"continued to {out2['losses'][-1]:.4f} on the smaller mesh")
    assert out2["losses"][-1] < out1["losses"][0]
    print("elastic re-mesh OK ✓")


if __name__ == "__main__":
    main()
