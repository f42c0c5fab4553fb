"""Trace reduction on a small synthetic trace, and the operation and byte
counts against values worked out by hand."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import counts  # noqa: E402
import harness  # noqa: E402
import tracing  # noqa: E402

STABLELM_2L = {"hidden_size": 5120,
               "num_hidden_layers": 2, "vocab_size": 12544,
               "intermediate_size": 13824, "num_attention_heads": 32,
               "num_key_value_heads": 8, "head_dim": 160}
RWKV6_24L = {"family": "ssm", "hidden_size": 2048, "num_hidden_layers": 24,
             "vocab_size": 65536, "intermediate_size": 7168,
             "head_size": 64, "time_decay_extra_dim": 64}
# Moonlight-16B-A3B cut to one chip's share of a layer split over 8 chips:
# the leading dense layer and 4 MoE layers, 8 of the 64 routed experts,
# 20480 of the 163840 vocabulary rows
MOONLIGHT_5L = {"hidden_size": 2048, "num_hidden_layers": 5,
                "vocab_size": 20480, "num_attention_heads": 16,
                "num_key_value_heads": 16, "kv_lora_rank": 512,
                "q_lora_rank": None, "qk_nope_head_dim": 128,
                "qk_rope_head_dim": 64, "v_head_dim": 128,
                "intermediate_size": 11264, "first_k_dense_replace": 1,
                "moe_layer_freq": 1, "n_routed_experts": 8,
                "n_shared_experts": 2, "num_experts_per_tok": 6,
                "moe_intermediate_size": 1408, "num_nextn_predict_layers": 0,
                "reduced": ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"],
                "published": {"num_hidden_layers": 27, "n_routed_experts": 64,
                              "vocab_size": 163840}}


def test_union_and_length():
    assert tracing.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tracing.length([(0, 1), (0.5, 2), (3, 4)]) == 3


def test_async_collective_pairs_and_exposure():
    ops = [
        ("fusion.1", 0.0, 1.0),
        ("collective-permute-start.3", 0.5, 0.6),
        ("fusion.2", 1.0, 2.0),
        ("collective-permute-done.3", 2.5, 2.7),   # start 0.5 -> done 2.7
        ("all-reduce.7", 3.0, 3.5),                # synchronous
        ("fusion.3", 3.2, 3.3),
    ]
    coll = tracing.collective_intervals(ops)
    assert sorted(coll) == [(0.5, 2.7), (3.0, 3.5)]
    s = tracing.device_summary(ops)
    assert s["busy_s"] == pytest.approx(2.0 + 0.2 + 0.5)
    assert s["collective_s"] == pytest.approx(2.2 + 0.5)
    # 0.5-2.7 less compute 0.5-2.0 is 0.7; 3.0-3.5 less 3.2-3.3 is 0.4
    assert s["exposed_collective_s"] == pytest.approx(1.1)


def test_hlo_names_and_containers():
    """Device events are named by their HLO text; a scan's loop contains
    its body's ops and adds no time of its own to the per-op totals."""
    ops = [
        ("%while.131 = (s32[]{:T(128)}, bf16[1,4096,5120]) while(...)", 0.0, 3.0),
        ("%fusion.2 = bf16[12544,5120]{1,0:T(8,128)(2,1)} fusion(...)", 0.5, 1.0),
        ("%collective-permute-start.3 = (f32[4]) collective-permute-start(...)",
         1.0, 1.1),
        ("%collective-permute-done.3 = f32[4] collective-permute-done(...)",
         2.0, 2.5),
    ]
    s = tracing.device_summary(ops)
    assert s["busy_s"] == pytest.approx(3.0)
    assert s["per_op"] == pytest.approx({
        "fusion.2 bf16[12544,5120]": 0.5,
        "collective-permute-start.3 (f32[4]": 0.1,
        "collective-permute-done.3 f32[4]": 0.5})
    assert s["collective_s"] == pytest.approx(1.5)
    # the loop around the collective is no compute that hides it
    assert s["exposed_collective_s"] == pytest.approx(1.5)


def test_idle_gaps_by_host_span():
    busy = [(1.0, 2.0), (3.0, 4.0)]
    spans = [("bench/input", 0.0, 1.0), ("bench/loss_read", 2.0, 3.0)]
    gaps = tracing.idle_gaps(busy, (0.0, 5.0), spans)
    assert gaps == {"bench/input": 1.0, "bench/loss_read": 1.0, "other": 1.0}


def test_idle_share_and_mfu_readers():
    run = {"trace": {"window_s": 2.0, "busiest": 0,
                     "devices": {0: {"busy_s": 1.5}}},
           "tokens_per_s": 10_000.0, "flops_per_token": 4e9, "chips": 1,
           "peaks": harness.peaks("TPU v5 lite")}
    assert harness.metric_reader("device_idle_share")(run) == \
        pytest.approx(25.0)
    assert harness.metric_reader("mfu")(run) == \
        pytest.approx(100 * 4e13 / 197e12)


def test_attention_kernel_roofline_reader():
    """The attention core's least time a step over the splash ops' time a
    step on the busiest chip; nothing without a splash op."""
    core = counts.attention_core(STABLELM_2L, 1, 4096)
    per_op = {"splash_mha_dkv_no_residuals.10 (f32[512,160]": 0.04,
              "splash_mha_dq_no_residuals.10 (f32[512,160]": 0.03,
              "splash_mha_fwd_residuals.4 (f32[512,160]": 0.03,
              "fusion.2 bf16[12544,5120]": 0.5}
    run = {"trace": {"busiest": 1, "devices": {
               0: {"per_op": {}}, 1: {"per_op": per_op}}},
           "trace_steps": 5, "attention_core": core,
           "peaks": harness.peaks("TPU v5 lite")}
    read = harness.metric_reader("attention_kernel_roofline")
    # 1.0308 TFLOP at 197 TFLOP/s (the 0.42 GB at 819 GB/s take less)
    # over 0.1 s / 5 steps of splash ops
    assert read(run) == pytest.approx(
        100 * (1_030_792_151_040 / 197e12) / 0.02)
    del per_op["splash_mha_dkv_no_residuals.10 (f32[512,160]"], \
        per_op["splash_mha_dq_no_residuals.10 (f32[512,160]"], \
        per_op["splash_mha_fwd_residuals.4 (f32[512,160]"]
    assert read(run) is None
    assert read(dict(run, attention_core=None)) is None


def test_stablelm_counts_by_hand():
    # per layer: q, o 5120 x 5120; k, v 5120 x 1280; gate, up, down
    # 5120 x 13824; head 5120 x 12544
    layer = 2 * 26_214_400 + 2 * 6_553_600 + 3 * 70_778_880
    assert counts.matmul_params(STABLELM_2L) == 2 * layer + 64_225_280 \
        == 619_970_560
    # 6 N + 3 (fwd + bwd) x 2 layers x 2 T (H hd): 251,658,240
    assert counts.model_flops_per_token(STABLELM_2L, 4096) == \
        6 * 619_970_560 + 251_658_240 == 3_971_481_600


@pytest.mark.parametrize("name, params, flops", [
    ("stablelm-12b", 619_970_560, 3_971_481_600),
    ("rwkv6-1.6b", 1_449_132_032, 8_732_540_928)])
def test_configuration_files_count_as_by_hand(name, params, flops):
    """The files as they are run, with every key they hold, give the
    counts worked out by hand (a cell's `mfu` reads this work)."""
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert counts.matmul_params(config) == params
    assert counts.model_flops_per_token(config, 4096) == flops


def test_rwkv6_counts_by_hand():
    # per layer: r, k, v, g, o and channel-mix r 2048^2; decay LoRA
    # 2048 x 64 twice; channel-mix k, v 2048 x 7168; head 2048 x 65536
    layer = 6 * 4_194_304 + 2 * 131_072 + 2 * 14_680_064
    assert counts.matmul_params(RWKV6_24L) == 24 * layer + 134_217_728 \
        == 1_449_132_032
    # recurrence: 3 x 24 layers x 4 x 2048 x 64
    assert counts.model_flops_per_token(RWKV6_24L, 4096) == \
        6 * 1_449_132_032 + 37_748_736


def test_wkv_forward_counts_by_hand():
    c = counts.wkv_forward(1, 32, 4096, 64)
    assert c["flops"] == 32 * 4096 * (4 * 64 * 64 + 2 * 64) == 2_164_260_864
    # r, k, v, log w, out: 5 x 32 x 4096 x 64 x 4 B; u; s0 and s_T
    assert c["bytes"] == 167_772_160 + 8_192 + 1_048_576
    t, bound = counts.roofline_s(c["flops"], c["bytes"],
                                 harness.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(168_828_928 / 819e9)


def test_stablelm_attention_core_by_hand():
    # 3 (fwd + bwd) x 2 layers x 32 heads x 4096^2 x (160 + 160), the
    # causal half of 2 (QK^T, PV) multiply-adds
    c = counts.attention_core(STABLELM_2L, 1, 4096)
    assert c["flops"] == 3 * 2 * 32 * 4096 ** 2 * 320 == 1_030_792_151_040
    # q, o, dO, dq: 32 heads; k, v, dk, dv: 8 heads; 160 wide, bf16
    assert c["bytes"] == 2 * 2 * 4096 * (4 * 32 + 4 * 8) * 160
    t, bound = counts.roofline_s(c["flops"], c["bytes"],
                                 harness.peaks("TPU v5 lite"))
    assert bound == "compute" and t == pytest.approx(5.2324e-3, rel=1e-4)
    assert counts.attention_core(RWKV6_24L, 1, 4096) is None


def test_moonlight_counts_by_hand():
    c = MOONLIGHT_5L
    # MLA: q 2048 x 16 x (128 + 64); kv down 2048 x (512 + 64); kv up
    # 512 x 16 x (128 + 128); o 16 x 128 x 2048
    mla = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    assert counts.attention_params(c) == mla == 13_762_560
    dense = 3 * 2048 * 11264
    assert dense == 69_206_016
    # router over all 64 experts; 2 shared experts; 6 routed experts a
    # token, of which 8 of 64 are held here
    router, shared, routed = 2048 * 64, 2 * 3 * 2048 * 1408, \
        6 * 8 * 3 * 2048 * 1408 // 64
    assert (router, shared, routed) == (131_072, 17_301_504, 6_488_064)
    assert router + shared + routed == 23_920_640
    assert counts.mlp_params(c) == dense + 4 * 23_920_640
    head = 2048 * 20480
    assert head == 41_943_040
    assert counts.matmul_params(c) == 5 * mla + dense + 4 * 23_920_640 \
        + head == 275_644_416
    # 3 x 5 layers x 16 heads x T x (192 + 128)
    assert counts.attention_core(c, 1, 8192)["flops"] // 8192 == \
        3 * 5 * 8192 * 16 * 320 == 629_145_600
    assert counts.model_flops_per_token(c, 8192) == \
        6 * 275_644_416 + 629_145_600 == 2_283_012_096


def test_mla_with_a_query_latent():
    # DeepSeek-V3's q_lora_rank 1536: q down 2048 x 1536, up 1536 x 16 x 192
    c = dict(MOONLIGHT_5L, q_lora_rank=1536)
    assert counts.attention_params(c) - counts.attention_params(
        MOONLIGHT_5L) == 2048 * 1536 + 1536 * 16 * 192 - 2048 * 16 * 192


@pytest.mark.parametrize("key, value", [
    ("sliding_window", 4096), ("layer_types", ["full", "sliding"]),
    ("num_local_experts", 8), ("linear_num_key_heads", 16),
    ("index_topk", 2048), ("num_nextn_predict_layers", 1)])
def test_a_key_no_formula_counts_raises(key, value):
    for config in (STABLELM_2L, MOONLIGHT_5L):
        with pytest.raises(ValueError, match=key):
            counts.model_flops_per_token(dict(config, **{key: value}), 4096)
    # null, false or 0 turn the layer off
    counts.matmul_params(dict(STABLELM_2L, **{key: None}))


def test_moe_layers_between_dense_ones_raise():
    with pytest.raises(ValueError, match="moe_layer_freq"):
        counts.matmul_params(dict(MOONLIGHT_5L, moe_layer_freq=2))


def test_an_attention_without_its_keys_raises():
    no_head = {k: v for k, v in STABLELM_2L.items() if k != "head_dim"}
    with pytest.raises(KeyError, match="head_dim"):
        counts.matmul_params(no_head)
