"""End-to-end training: auto engine loss decrease, manual (ZeRO-3 +
plan-selected collectives) engine equivalence, checkpoint/restart replay.

Multi-device cases run in a subprocess with 8 fake devices."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.train import TrainConfig, run_training


def test_auto_engine_loss_decreases(tmp_path):
    out = run_training(TrainConfig(
        arch="stablelm-12b", steps=30, seq_len=64, global_batch=4,
        lr=3e-3, log_every=1000))
    losses = out["losses"]
    assert losses[-1] < losses[0] - 0.3, (losses[0], losses[-1])


@pytest.mark.parametrize("seq_len,paths", [(512, "kernel=1 xla=0"),
                                            (64, "kernel=0 xla=1")])
def test_trainer_prints_attention_paths_once(seq_len, paths):
    """After its first step the trainer names the path its attention can
    take: the splash kernels where 512 divides the sequence."""
    from repro.runtime.metrics import MetricsRegistry, set_default_metrics
    logs = []
    old = set_default_metrics(MetricsRegistry())
    try:
        run_training(TrainConfig(arch="stablelm-12b", steps=2,
                                 seq_len=seq_len, global_batch=1,
                                 log_every=1000), on_log=logs.append)
    finally:
        set_default_metrics(old)
    assert [s for s in logs if s.startswith("attention paths")] == [
        f"attention paths (calls traced): {paths}"]


def test_ckpt_restart_replays_exactly(tmp_path):
    tc = dict(arch="stablelm-12b", steps=20, seq_len=32, global_batch=2,
              lr=1e-3, ckpt_every=10, log_every=1000)
    full = run_training(TrainConfig(**tc, ckpt_dir=str(tmp_path / "full")))
    # interrupted run: first do 10 steps, then resume to 20 from disk
    part = run_training(TrainConfig(**{**tc, "steps": 10},
                                    ckpt_dir=str(tmp_path / "part")))
    resumed = run_training(TrainConfig(**tc,
                                       ckpt_dir=str(tmp_path / "part")))
    assert resumed["losses"][-1] == pytest.approx(full["losses"][-1],
                                                  rel=1e-5)


_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.launch.train import TrainConfig, run_training
from repro.launch.mesh import make_mesh

results = {}
mesh = make_mesh((8, 1), ("data", "model"))
kw = dict(arch="rwkv6-1.6b", steps=8, seq_len=32, global_batch=8,
          lr=1e-3, log_every=1000)
auto = run_training(TrainConfig(**kw, engine="auto"), mesh=mesh)
for sync in ("psum", "ring", "hcps", "gentree"):
    tc = TrainConfig(**kw, engine="manual", sync=sync)
    if sync == "hcps":
        tc = TrainConfig(**kw, engine="manual", sync=sync)
    man = run_training(tc, mesh=mesh)
    diff = abs(man["losses"][-1] - auto["losses"][-1])
    results[f"manual_{sync}_diff"] = diff
    results[f"manual_{sync}_ok"] = bool(diff < 5e-2)
results["auto_final"] = auto["losses"][-1]
results["auto_decreased"] = bool(auto["losses"][-1] < auto["losses"][0])

# TP mesh: auto engine with model axis > 1
mesh_tp = make_mesh((2, 4), ("data", "model"))
tp = run_training(TrainConfig(arch="stablelm-12b", steps=6, seq_len=32,
                              global_batch=4, lr=1e-3, log_every=1000),
                  mesh=mesh_tp)
results["tp_finite"] = bool(np.isfinite(tp["losses"][-1]))
print("RESULTS " + json.dumps(results))
"""


@pytest.fixture(scope="module")
def multi():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _DRIVER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


def test_manual_engines_match_auto(multi):
    for sync in ("psum", "ring", "hcps", "gentree"):
        assert multi[f"manual_{sync}_ok"], multi


def test_auto_multi_device_decreases(multi):
    assert multi["auto_decreased"]


def test_tp_mesh_trains(multi):
    assert multi["tp_finite"]


def test_hcps_factors_plumb_through():
    """SyncConfig with explicit factors must not crash plan building."""
    from repro.core.sync import SyncConfig, plan_axes_gentree
    plans = plan_axes_gentree([("data", 16), ("pod", 2)], 1e8)
    assert all(p.strategy in ("psum", "ring", "rhd", "cps", "hcps")
               for p in plans)
    assert len(plans) == 2
