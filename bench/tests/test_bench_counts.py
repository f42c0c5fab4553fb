"""Trace reduction on a small synthetic trace, and the operation and byte
counts against values worked out by hand."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import counts  # noqa: E402
import harness  # noqa: E402
import tracing  # noqa: E402

STABLELM_2L = {"family": "dense", "hidden_size": 5120,
               "num_hidden_layers": 2, "vocab_size": 12544,
               "intermediate_size": 13824, "num_attention_heads": 32,
               "num_key_value_heads": 8, "head_dim": 160}
RWKV6_24L = {"family": "ssm", "hidden_size": 2048, "num_hidden_layers": 24,
             "vocab_size": 65536, "intermediate_size": 7168,
             "head_size": 64, "time_decay_extra_dim": 64}


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


def test_stablelm_counts_by_hand():
    # per layer: q, o 5120 x 5120; k, v 5120 x 1280; gate, up, down
    # 5120 x 13824; head 5120 x 12544
    layer = 2 * 26_214_400 + 2 * 6_553_600 + 3 * 70_778_880
    assert counts.matmul_params(STABLELM_2L) == 2 * layer + 64_225_280 \
        == 619_970_560
    # 6 N + 3 (fwd + bwd) x 2 layers x 2 T (H hd): 251,658,240
    assert counts.model_flops_per_token(STABLELM_2L, 4096) == \
        6 * 619_970_560 + 251_658_240 == 3_971_481_600


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
