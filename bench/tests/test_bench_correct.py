"""The comparison that decides `correct`, at a size a test run holds (the
configuration file's `tiny` sizes), on the CPU: the harness's look for a
chip is skipped and the rest of a run is driven with the timed path
broken underneath; each fault that a cell can have, and the control (the
reference in float8 put in the program's place), must come out not
correct under the cell's own limits."""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402

CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"] if w["chips"] == 1]


def tiny(name: str) -> dict:
    c = copy.deepcopy(harness.cell(name))
    c["config"] = harness.at_test_size(c["config"])
    c["workload"]["traffic"]["seq_len"] = 64
    return c


def drive(c, fault=None):
    import jax
    drv = harness.load_module(c["driver"])
    return drv.run(c, 2**31 + 12345, 0.3, False, t_start=time.perf_counter(),
                   devices=jax.devices()[:c["entry"]["chips"]], fault=fault)


@pytest.fixture(scope="module", params=CELLS)
def sound(request):
    """(cell, a sound run of it at the tiny size), one run a cell."""
    return request.param, drive(tiny(request.param))


def test_sound_run_reads_its_numbers(sound):
    cell, out = sound
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["checks"]["input_mismatch"]["value"] == 0
    assert set(out["checks"]) == set(harness.cell(cell)["workload"]["limits"])
    for ch in out["checks"].values():
        assert ch["value"] is not None and ch["value"] >= 0


def test_sound_run_records_the_cell(sound):
    """The record carries what a new configuration's metric files read:
    the configuration as run, the traffic, the program's ModelConfig, the
    window's counters and the last step's outputs but its loss."""
    import dataclasses
    cell, out = sound
    rec, c = out["record"], tiny(cell)
    assert out["failed"] == 0
    assert rec["config"] == c["config"]
    assert rec["traffic"] == c["workload"]["traffic"]
    drv = harness.load_module(c["driver"])
    assert rec["model_config"] == dataclasses.asdict(
        drv.model_config(c["config"]))
    assert rec["model_config"]["d_model"] == c["config"]["hidden_size"]
    assert set(rec["step_outputs"]) == {"gnorm"}
    assert rec["step_outputs"]["gnorm"] > 0
    assert rec["counters"] and all(isinstance(v, float)
                                   for v in rec["counters"].values())
    # the step was traced in set-up and never again in the window
    traced = [k for k in rec["counters"]
              if k.startswith("attention_path_total")]
    assert traced and all(rec["counters"][k] == 0 for k in traced)


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_step_is_not_correct(cell, fault):
    out = drive(tiny(cell), fault)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The reference with float8 products against the reference."""
    import oracle
    c = tiny(cell)
    drv = harness.load_module(c["driver"])
    import jax
    tn = drv.Trainer(c, jax.devices()[:1])
    tn.start(2**31 + 99)
    want = tn.reference()
    got = tn.reference(mm=oracle.FP8)
    ok, checks = harness.judge(oracle.gaps(got, want),
                               {k: v for k, v in c["workload"]["limits"].items()
                                if k != "input_mismatch"})
    assert ok is False, checks


def test_rwkv6_reference_follows_the_program():
    """The rwkv6-1.6b reference (staged for its four-chip cell) takes the
    program's one-device steps at a tiny size: the same losses to bf16
    rounding, and gradients of the same norms."""
    c = tiny(CELLS[0])
    c["config"] = harness.at_test_size(json.loads(
        (BENCH / "configs" / "rwkv6-1.6b.json").read_text()))
    c["reference"] = BENCH / "configs" / "rwkv6-1.6b.ref.py"
    c["workload"]["traffic"]["seq_len"] = 128
    out = drive(c)
    assert out["checks"]["loss_gap"]["value"] < 5e-3
    assert out["checks"]["grad_gap"]["value"] < 0.1
