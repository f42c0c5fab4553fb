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
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
# Moonlight-16B-A3B (its published config.json) cut to one chip's share of
# a layer split over 8 chips: latent attention, 8 of 64 routed experts, the
# leading dense layer and 4 MoE layers, 20480 of 163840 vocabulary rows
MOONLIGHT = {
    "name": "moonlight-16b-a3b",
    "source": "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/"
              "main/config.json",
    "arch": "deepseek-moe-16b",
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 11264, "kv_lora_rank": 512,
    "max_position_embeddings": 8192, "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 8,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 5,
    "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 20480,
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "published": {"num_hidden_layers": 27, "n_routed_experts": 64,
                  "vocab_size": 163840},
    "program": {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
                "n_heads": "num_attention_heads",
                "n_kv_heads": "num_key_value_heads",
                "d_ff": "intermediate_size", "vocab": "vocab_size",
                "n_experts": "published.n_routed_experts",
                "n_shared_experts": "n_shared_experts",
                "top_k": "num_experts_per_tok",
                "d_ff_expert": "moe_intermediate_size"},
    "tiny": {"hidden_size": 64, "num_attention_heads": 4,
             "num_key_value_heads": 4, "kv_lora_rank": 16,
             "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "intermediate_size": 128,
             "moe_intermediate_size": 32, "n_routed_experts": 2,
             "num_experts_per_tok": 2, "num_hidden_layers": 2,
             "vocab_size": 512, "published": {"n_routed_experts": 4}},
}


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


def test_a_new_configuration_is_files_alone(tmp_path):
    """A copy of the benchmark with a Moonlight-shaped configuration (latent
    attention, a share of the experts, a leading dense layer), its cell and
    its workload, added as files and entries only: the cell resolves, the
    file counts, its `program` map reads the published expert count, and
    its `tiny` sizes make the CPU tests' configuration."""
    import counts
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    file = f"bench/configs/{MOONLIGHT['name']}.json"
    bm["configs"].append({"name": MOONLIGHT["name"],
                          "source": MOONLIGHT["source"], "file": file,
                          "reduced": MOONLIGHT["reduced"],
                          "why": "latent attention, a share of the experts"})
    name = MOONLIGHT["name"] + ".train-8k"
    bm["workloads"].append({"name": name, "config": MOONLIGHT["name"],
                            "traffic": "train-8k", "chips": 1,
                            "why": "1 x 8192 tokens a step"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    (tmp_path / file).write_text(json.dumps(MOONLIGHT))
    old = BM["workloads"][0]["name"]
    wl = json.loads((BENCH / "workloads" / f"{old}.json").read_text())
    wl["traffic"]["seq_len"] = 8192
    (tmp_path / "bench" / "workloads" / f"{name}.json").write_text(
        json.dumps(wl))

    c = harness.cell(name, root=tmp_path)
    assert c["config"] == MOONLIGHT
    assert c["workload"]["traffic"]["seq_len"] == 8192
    assert c["driver"] == tmp_path / "bench" / "drivers" / "train.py"
    assert c["reference"] == tmp_path / file.replace(".json", ".ref.py")
    assert counts.matmul_params(c["config"]) == 275_644_416
    assert counts.model_flops_per_token(c["config"], 8192) == 2_283_012_096

    drv = harness.load_module(c["driver"], "bench_copy_drivers_train")
    cfg = drv.model_config(c["config"])
    assert (cfg.name, cfg.n_experts, cfg.top_k, cfg.n_shared_experts,
            cfg.d_ff_expert, cfg.n_layers, cfg.vocab) == (
        "deepseek-moe-16b", 64, 6, 2, 1408, 5, 20480)

    small = harness.at_test_size(c["config"])
    assert small["hidden_size"] == 64 and small["n_routed_experts"] == 2
    assert small["published"] == {"num_hidden_layers": 27,
                                  "n_routed_experts": 4, "vocab_size": 163840}
    assert drv.model_config(small).n_experts == 4
    # 2 of 4 experts held: the routed share is half of 2 experts a token
    assert counts.mlp_params(small) == 3 * 64 * 128 + (
        64 * 4 + 2 * 3 * 64 * 32 + 2 * 2 * 3 * 64 * 32 / 4)
    assert c["config"]["tiny"]["published"] == {"n_routed_experts": 4}


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
