"""Operations and bytes that the algorithms need, computed from shapes.

Counted in floating-point operations (a multiply-add is 2) of the model's
forward and backward pass, never of what an implementation recomputes.

A configuration is counted by the keys its file holds, as the published
config.json names them, not by a family name:

  attention  `kv_lora_rank`: latent attention (MLA, arXiv:2412.19437), with
             `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim` and
             `q_lora_rank` (null: q projected directly); otherwise grouped-
             query attention (`num_key_value_heads`, `head_dim`);
  MLP        `intermediate_size` for the first `first_k_dense_replace`
             layers (0 when absent), and where `n_routed_experts` is given
             a mixture of experts in every later layer; otherwise dense in
             every layer;
  family     `ssm` alone: the RWKV-6 block (time mix, channel mix and the
             WKV recurrence).

A file with a key of a layer these counts do not model (`UNCOUNTED`)
raises, naming it.
"""
from __future__ import annotations

import re

# keys of attention or MLP kinds counted by none of the formulas here:
# windowed or chunked attention, layers of several kinds, linear and
# state-space mixers, sparse-attention indexers, experts under other names,
# multi-token prediction layers
UNCOUNTED = re.compile(
    r"(use_)?sliding_window.*|swa_.*|window_size|max_window_layers"
    r"|attention_chunk_size|layer_types|.*layers_block_type|hybrid_.*"
    r"|full_attention_interval|global_attn_every_n_layers|attn_layer_.*"
    r"|expert_layer_.*|decoder_sparse_step|mlp_only_layers"
    r"|interleave_moe_layer_step|linear_.*|mamba_.*|ssm_.*|kda_.*"
    r"|lightning_.*|index_.*|indexer_.*|num_local_experts|num_experts"
    r"|moe_num_experts|.*shared_expert_intermediate_size"
    r"|shared_intermediate_size|zero_expert_num|o_lora_rank"
    r"|num_nextn_predict_layers|mtp_.*")


def check_counted(config: dict) -> None:
    """Raise on a key of a layer that no formula here counts; a key set to
    null, false or 0 turns its layer off and is no such key."""
    for key, value in config.items():
        if value and UNCOUNTED.fullmatch(key):
            raise ValueError(f"no count for {key!r} = {value!r}")
    if config.get("moe_layer_freq", 1) != 1:
        raise ValueError(
            f"no count for 'moe_layer_freq' = {config['moe_layer_freq']!r}")


def attention_shape(config: dict) -> tuple[int, int, int, int]:
    """(query heads, key and value heads, q/k head width, v head width) of
    the attention core: latent attention's keys and values are per query
    head once projected up from the latent."""
    H = config["num_attention_heads"]
    if "kv_lora_rank" in config:
        return (H, H, config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
                config["v_head_dim"])
    hd = config["head_dim"]
    return H, config["num_key_value_heads"], hd, hd


def attention_params(config: dict) -> int:
    """Matmul parameters of one attention layer's projections."""
    d, H = config["hidden_size"], config["num_attention_heads"]
    if "kv_lora_rank" in config:
        r_kv, r_q = config["kv_lora_rank"], config.get("q_lora_rank")
        nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
        v = config["v_head_dim"]
        q = (d * r_q + r_q * H * (nope + rope) if r_q
             else d * H * (nope + rope))
        kv = d * (r_kv + rope) + r_kv * H * (nope + v)    # down, then up
        return q + kv + H * v * d                         # ... and o
    hd, Hkv = config["head_dim"], config["num_key_value_heads"]
    return d * H * hd + 2 * d * Hkv * hd + H * hd * d     # q, k, v, o


def mlp_params(config: dict) -> float:
    """Matmul parameters of every layer's MLP, summed: gate, up and down.
    A mixture-of-experts layer counts its router over all the published
    experts, its shared experts, and its routed experts' expected share
    of the work under uniform routing: `num_experts_per_tok` experts a
    token, of which the chip holds E_held of E_total (`n_routed_experts`,
    and its `published` value where `reduced` cuts it)."""
    d, L = config["hidden_size"], config["num_hidden_layers"]
    dense = 3 * d * config["intermediate_size"]
    if "n_routed_experts" not in config:
        return L * dense
    k = config.get("first_k_dense_replace", 0)
    held = config["n_routed_experts"]
    total = (config["published"]["n_routed_experts"]
             if "n_routed_experts" in config["reduced"] else held)
    expert = 3 * d * config["moe_intermediate_size"]
    moe = (d * total + config.get("n_shared_experts", 0) * expert
           + config["num_experts_per_tok"] * held * expert / total)
    return k * dense + (L - k) * moe


def matmul_params(config: dict) -> float:
    """Parameters that take part in a matrix product per token: every
    projection of every layer and the LM head; not the embedding, which
    is a gather, nor norm gains, decays or mixing coefficients. A mixture
    of experts counts what a token uses (`mlp_params`)."""
    check_counted(config)
    d, L, V = (config["hidden_size"], config["num_hidden_layers"],
               config["vocab_size"])
    if config.get("family") == "ssm":
        f = config["intermediate_size"]
        lora = config["time_decay_extra_dim"]
        # receptance, key, value, gate, output; decay LoRA down and up;
        # channel-mix key, value and receptance
        layer = 5 * d * d + 2 * d * lora + 2 * d * f + d * d
        return L * layer + d * V
    return L * attention_params(config) + mlp_params(config) + d * V


def attention_core(config: dict, seqs: int, seq_len: int) -> dict | None:
    """The attention core of one training step over `seqs` sequences of
    `seq_len` tokens, all layers: softmax(Q K^T) V, not the projections.
      flops: QK^T and PV over the causal half, seqs x H x T^2 / 2 x 2 x
             (d_qk + d_v) forward per layer, times 3 for forward and
             backward; recomputation not counted;
      bytes: q, k, v, o and dO read and dq, dk, dv written, once each, in
             bfloat16.
    None for a model without softmax attention (`family` ssm)."""
    if config.get("family") == "ssm":
        return None
    L = config["num_hidden_layers"]
    H, Hkv, d_qk, d_v = attention_shape(config)
    flops = 3 * L * seqs * H * seq_len * seq_len * (d_qk + d_v)
    # (q, o, dO, dq: H heads; k, v, dk, dv: Hkv heads) x T tokens x 2 B
    bytes_ = 2 * L * seqs * seq_len * (2 * H * d_qk + 2 * H * d_v
                                       + 2 * Hkv * (d_qk + d_v))
    return {"flops": flops, "bytes": bytes_}


def model_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward operations per trained token: 6 per matmul
    parameter, plus
      attention: the attention core of one sequence (`attention_core`)
                 over its T tokens;
      ssm:       the WKV recurrence, 4 x d x head_size forward per layer
                 (the state's decay-and-add of k^T v and the read-out by r,
                 each head_size^2 multiply-adds per head), times 3."""
    n = 6 * matmul_params(config)
    core = attention_core(config, 1, seq_len)
    if core is not None:
        return n + core["flops"] // seq_len
    L, hd = config["num_hidden_layers"], config["head_size"]
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
