"""Operations and bytes that the algorithms need, computed from shapes.

Counted in floating-point operations (a multiply-add is 2) of the model's
forward and backward pass, never of what an implementation recomputes.
"""
from __future__ import annotations


def matmul_params(config: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    projection of every layer and the LM head; not the embedding, which
    is a gather, nor norm gains, decays or mixing coefficients."""
    d, L, V = (config["hidden_size"], config["num_hidden_layers"],
               config["vocab_size"])
    f = config["intermediate_size"]
    if config["family"] == "dense":
        H, Hkv, hd = (config["num_attention_heads"],
                      config["num_key_value_heads"], config["head_dim"])
        attn = d * H * hd + 2 * d * Hkv * hd + H * hd * d
        layer = attn + 3 * d * f                 # q, k, v, o; gate, up, down
    elif config["family"] == "ssm":
        lora = config["time_decay_extra_dim"]
        # receptance, key, value, gate, output; decay LoRA down and up;
        # channel-mix key, value and receptance
        layer = 5 * d * d + 2 * d * lora + 2 * d * f + d * d
    else:
        raise ValueError(f"no count for family {config['family']!r}")
    return L * layer + d * V


def model_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward operations per trained token: 6 per matmul
    parameter, plus
      dense: QK^T and AV over the causal half of the context, 2 x T x
             (H x head_dim) forward per layer, times 3;
      ssm:   the WKV recurrence, 4 x d x head_size forward per layer (the
             state's decay-and-add of k^T v and the read-out by r, each
             head_size^2 multiply-adds per head), times 3."""
    L = config["num_hidden_layers"]
    n = 6 * matmul_params(config)
    if config["family"] == "dense":
        width = config["num_attention_heads"] * config["head_dim"]
        return n + 3 * L * 2 * seq_len * width
    hd = config["head_size"]
    return n + 3 * L * 4 * config["hidden_size"] * hd


def wkv_forward(batch: int, heads: int, seq_len: int, head: int,
                in_bytes: int = 4) -> dict:
    """One call of the WKV forward kernel at (B, H, T, K=V=head):
      flops: per token and head, the state update S = diag(w) S + k^T v
             (2 K V: a multiply for the decay, a multiply-add for k^T v)
             and the read-out r (S + diag(u) k^T v) (2 K V + 2 K);
      bytes: r, k, v, log w and the output, each B H T K in `in_bytes`,
             u, and the initial and final state (B H K V, float32);
             each read or written once."""
    B, H, T, K = batch, heads, seq_len, head
    flops = B * H * T * (4 * K * K + 2 * K)
    bytes_ = 5 * B * H * T * K * in_bytes + H * K * 4 + 2 * B * H * K * K * 4
    return {"flops": flops, "bytes": bytes_}


def roofline_s(flops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """The least time on a chip of `peak`, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = bytes_ / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
