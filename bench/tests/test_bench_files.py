"""The benchmark's files: every configuration, workload and per-layer
metric that BENCHMARK.json names is found by name and loads, the file
keeps to its format, and a new cell or metric needs nothing but files
and entries."""
from __future__ import annotations

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BM = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert BM["command"][1] == "bench/run.py"
    assert 1 <= BM["run_seconds"] <= 51


def test_names_units_and_bounds():
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(names) == len(set(names))
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BM["end_to_end"])
    e2e = {m["name"] for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        assert len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_cells():
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 2)
    pairs = {(w["config"], w["traffic"]) for w in BM["workloads"]}
    assert len(pairs) == len(BM["workloads"])
    used = {w["config"] for w in BM["workloads"]}
    assert used == {c["name"] for c in BM["configs"]}
    for w in BM["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_cell_found_by_name(cell):
    c = harness.cell(cell)
    assert c["driver"].is_file() and c["reference"].is_file()
    ref = harness.load_module(c["reference"])
    assert callable(ref.init_params) and callable(ref.loss)
    drv = harness.load_module(c["driver"])
    assert callable(drv.run)
    assert c["workload"]["limits"], "a cell without limits is never judged"
    reported = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert c["per_layer"]


@pytest.mark.parametrize("conf", BM["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    f = ROOT / conf["file"]
    cfg = json.loads(f.read_text())
    assert f.is_relative_to(BENCH)
    assert cfg["source"] == conf["source"]
    assert sorted(cfg["reduced"]) == sorted(conf["reduced"])
    for key in conf["reduced"]:
        assert key in cfg and key in cfg["published"]
        assert not key.endswith(("_dim", "_rank", "_size")) or \
            key == "vocab_size"


@pytest.mark.parametrize("metric", [m["name"] for m in BM["per_layer"]])
def test_metric_reader_found_by_name(metric):
    read = harness.metric_reader(metric)
    assert read({}) is None          # nothing to read: nothing returned


def test_a_new_cell_is_files_alone(tmp_path):
    """A copy of the benchmark with one more cell and one more metric,
    added as files and entries only, resolves them by name."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    old = bm["workloads"][0]
    new = dict(old, name=old["config"] + ".train-2k", traffic="train-2k")
    bm["workloads"].append(new)
    bm["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "model step",
        "moves": bm["end_to_end"][0]["name"], "workloads": [new["name"]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    wl = json.loads((BENCH / "workloads" / f"{old['name']}.json").read_text())
    wl["traffic"]["seq_len"] = 2048
    (tmp_path / "bench" / "workloads" / f"{new['name']}.json").write_text(
        json.dumps(wl))
    (tmp_path / "bench" / "metrics" / "steps_in_window.py").write_text(
        "def read(run):\n    return run.get('steps')\n")

    c = harness.cell(new["name"], root=tmp_path)
    assert c["workload"]["traffic"]["seq_len"] == 2048
    assert [m["name"] for m in c["per_layer"]][-1] == "steps_in_window"
    assert harness.metric_reader("steps_in_window", root=tmp_path)(
        {"steps": 7}) == 7
    # the cell that was there sees no metric it does not report
    assert "steps_in_window" not in [
        m["name"] for m in harness.cell(old["name"], root=tmp_path)["per_layer"]]


def test_unknown_device_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v0 imaginary")
    v5e = harness.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_seed_key_keeps_high_bits():
    import numpy as np
    a = np.asarray(harness.seed_key(5))
    b = np.asarray(harness.seed_key(5 + (1 << 32)))
    assert not (a == b).all()
