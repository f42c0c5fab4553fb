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
# leading dense layer and 4 MoE layers, 20480 of 163840 vocabulary rows.
# Its `arch` is its own: Moonlight registers its own ModelConfig, so that no
# field is inherited from another architecture (deepseek-moe-16b's
# `moe_groups` = 16 would pick the grouped capacity dispatch, which drops
# tokens). `scopes` lists the names its program opens inside the layer
# scopes.
MOONLIGHT = {
    "name": "moonlight-16b-a3b",
    "source": "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/"
              "main/config.json",
    "arch": "moonlight-16b-a3b",
    "scopes": ["model/mlp/router", "model/mlp/experts", "model/attn/latent"],
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


def _register_moonlight(monkeypatch):
    """What Moonlight's own PR adds to the program: its ModelConfig,
    registered under its `arch`."""
    import types
    from repro.models.config import ModelConfig
    mod = types.ModuleType("repro.configs.moonlight_16b_a3b")
    mod.CONFIG = ModelConfig(
        name="moonlight-16b-a3b", family="moe", n_layers=27, d_model=2048,
        n_heads=16, n_kv_heads=16, d_ff=11264, vocab=163840, n_experts=64,
        n_shared_experts=2, top_k=6, d_ff_expert=1408)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)


# the metric files a Moonlight PR brings: a nested scope, a counter and a
# step output, and a grouped expert matmul's roofline from the record's
# configuration and traffic
MOONLIGHT_METRICS = {
    "expert_ms_per_step": ('''"""Device ms per traced step under the
`model/mlp/experts` scope the configuration file lists."""
import scopes


def read(run):
    return scopes.ms_per_scope(run, ("model/mlp/experts",))
''', "ms", "lower", "device_trace", "MLP"),
    "expert_load_max_over_mean": ('''"""Tokens of the busiest held expert in
the last window step (step output `expert_tokens_max`) over a held
expert's mean a window step (counter `moe_expert_tokens_total`)."""


def read(run):
    total = (run.get("counters") or {}).get("moe_expert_tokens_total")
    top = (run.get("step_outputs") or {}).get("expert_tokens_max")
    if not total or top is None:
        return None
    return top / (total / run["steps"] / run["config"]["n_routed_experts"])
''', "x", "lower", "program_counter", "MLP"),
    "expert_gmm_roofline": ('''"""Share, in %, of the grouped expert
matmul's time that the roofline needs: the routed tokens of one chip's
sequences (`traffic`), each to `num_experts_per_tok` experts, the held
share of them, through 3 matmuls of `hidden_size` x `moe_intermediate_size`
in every MoE layer (`config`), 6 FLOP a weight and token forward and
backward; bf16 weights and token rows read 3 times; over ops named `gmm`."""
import counts


def read(run):
    tr, peak = run.get("trace"), run.get("peaks")
    c, t = run.get("config"), run.get("traffic")
    if not tr or not peak or not c or not t:
        return None
    per_op = tr["devices"][tr["busiest"]]["per_op"]
    kernel_s = sum(s for op, s in per_op.items() if op.startswith("gmm"))
    if not kernel_s:
        return None
    held = c["n_routed_experts"] / c["published"]["n_routed_experts"]
    routed = (t["sequences_per_chip"] * t["seq_len"]
              * c["num_experts_per_tok"] * held)
    layers = c["num_hidden_layers"] - c.get("first_k_dense_replace", 0)
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    flops = 6 * routed * 3 * d * f * layers
    bytes_ = 3 * 2 * layers * (c["n_routed_experts"] * 3 * d * f
                               + routed * (2 * d + 2 * f))
    least_s, _ = counts.roofline_s(flops, bytes_, peak)
    return 100.0 * least_s / (kernel_s / run["trace_steps"])
''', "%", "higher", "device_trace", "kernels"),
}


def _moonlight_copy(tmp_path: Path) -> str:
    """A copy of the benchmark with the Moonlight-shaped configuration, its
    cell, its workload and its metric files, added as files and entries
    only; the cell's name."""
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
    for metric, (code, unit, better, source, layer) in \
            MOONLIGHT_METRICS.items():
        bm["per_layer"].append({
            "name": metric, "unit": unit, "better": better,
            "source": source, "layer": layer, "moves": "tokens_per_s",
            "workloads": [name]})
        (tmp_path / "bench" / "metrics" / f"{metric}.py").write_text(code)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    (tmp_path / file).write_text(json.dumps(MOONLIGHT))
    old = BM["workloads"][0]["name"]
    wl = json.loads((BENCH / "workloads" / f"{old}.json").read_text())
    wl["traffic"]["seq_len"] = 8192
    (tmp_path / "bench" / "workloads" / f"{name}.json").write_text(
        json.dumps(wl))
    return name


def _bench_files(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts
            and ".cache" not in p.parts}


def test_a_new_configuration_is_files_alone(tmp_path, monkeypatch):
    """A copy of the benchmark with a Moonlight-shaped configuration (latent
    attention, a share of the experts, a leading dense layer), its cell and
    its workload, added as files and entries only: the cell resolves, the
    file counts, its `program` map reads the published expert count over
    its own registered ModelConfig, and its `tiny` sizes make the CPU
    tests' configuration."""
    import counts
    _register_moonlight(monkeypatch)
    name = _moonlight_copy(tmp_path)
    file = f"bench/configs/{MOONLIGHT['name']}.json"

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
        "moonlight-16b-a3b", 64, 6, 2, 1408, 5, 20480)
    assert cfg.moe_groups == 0         # nothing of deepseek-moe-16b's

    small = harness.at_test_size(c["config"])
    assert small["hidden_size"] == 64 and small["n_routed_experts"] == 2
    assert small["published"] == {"num_hidden_layers": 27,
                                  "n_routed_experts": 4, "vocab_size": 163840}
    assert drv.model_config(small).n_experts == 4
    # 2 of 4 experts held: the routed share is half of 2 experts a token
    assert counts.mlp_params(small) == 3 * 64 * 128 + (
        64 * 4 + 2 * 3 * 64 * 32 + 2 * 2 * 3 * 64 * 32 / 4)
    assert c["config"]["tiny"]["published"] == {"n_routed_experts": 4}


def test_a_new_configuration_brings_its_metrics_as_files(tmp_path):
    """The Moonlight-shaped copy's three metric files (a scope its file
    lists, a counter with a step output, a kernel's roofline counted from
    the record's configuration and traffic) resolve by name and read a
    synthetic record, with every file of the benchmark that was there
    unchanged."""
    name = _moonlight_copy(tmp_path)
    before = _bench_files(BENCH)
    after = _bench_files(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
    assert {str(k) for k in set(after) - set(before)} == {
        f"configs/{MOONLIGHT['name']}.json", f"workloads/{name}.json",
        *(f"metrics/{m}.py" for m in MOONLIGHT_METRICS)}

    c = harness.cell(name, root=tmp_path)
    assert [m["name"] for m in c["per_layer"]][-3:] == list(MOONLIGHT_METRICS)
    assert not set(MOONLIGHT_METRICS) & {
        m["name"] for m in harness.cell(BM["workloads"][0]["name"],
                                        root=tmp_path)["per_layer"]}
    peak = harness.peaks("TPU v5 lite")
    # 5 traced steps of 25.6 ms of `gmm` ops; 4 MoE layers; 8192 tokens x 6
    # experts x 8/64 held = 6144 routed rows
    flops = 6 * 6144 * 3 * 2048 * 1408 * 4
    assert flops == 1_275_605_286_912
    record = {
        "steps": 40, "trace_steps": 5, "config": c["config"],
        "traffic": c["workload"]["traffic"], "peaks": peak,
        "counters": {"moe_expert_tokens_total": 40 * 6144.0,
                     "plan_cache_hits_total": 3.0},
        "step_outputs": {"gnorm": 0.5, "expert_tokens_max": 1536.0},
        "trace": {"busiest": 0, "devices": {0: {"per_op": {
            "gmm.1": 0.1, "gmm.2": 0.028, "fusion.3": 0.5}}}},
        "scopes": {"by_scope": {
            "model/mlp/experts": {"fwd": 0.02, "bwd": 0.04, "remat": 0.015},
            "model/mlp": {"fwd": 1.0, "bwd": 0.0, "remat": 0.0}}},
    }

    def read(metric, run):
        return harness.metric_reader(metric, root=tmp_path)(run)
    assert read("expert_ms_per_step", record) == pytest.approx(15.0)
    # 1536 tokens on one expert against 6144 / 8 = 768 on the mean one
    assert read("expert_load_max_over_mean", record) == pytest.approx(2.0)
    least = flops / peak["bf16_flops_per_s"]
    assert read("expert_gmm_roofline", record) == pytest.approx(
        100 * least / 0.0256)
    for metric in MOONLIGHT_METRICS:      # nothing to read, nothing returned
        assert read(metric, {"trace_steps": 5, "scopes": {}}) is None


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
