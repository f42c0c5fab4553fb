"""Shared transformer layers: GQA attention (causal / sliding-window /
softcap / qk-norm / RoPE / M-RoPE), SwiGLU MLP, MoE (dense-masked and
sorted-dispatch), RMSNorm.

Attention never materializes a (T, T) score tensor: the train/prefill path
scans over query blocks (online softmax against the full K for global
layers; a banded KV slice for sliding-window layers, making local layers
O(T·W)). This is the flash algorithm expressed in XLA ops so it lowers on
any backend. Causal global self-attention lowered for a TPU instead runs
JAX's splash attention kernels (forward and backward, masked blocks
skipped, K/V shared by each GQA group); `attention_path_total` counts
which of the two each traced call can take.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig

Params = dict[str, Any]
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, scale: float | None = None, dtype=jnp.bfloat16):
    fan_in = shape[-2] if len(shape) > 1 else shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------
def _rope_freqs(d: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float
               ) -> jax.Array:
    """x: (..., T, D_head); positions: broadcastable to (..., T)."""
    d = x.shape[-1]
    freqs = _rope_freqs(d, theta)                       # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: tuple[int, ...]) -> jax.Array:
    """Qwen2-VL multimodal RoPE. x: (B, H, T, D); positions: (3, B, T) —
    one position stream per (t, h, w) section of the rotary dims."""
    d = x.shape[-1]
    half = d // 2
    freqs = _rope_freqs(d, theta)                       # (half,)
    # section s owns freqs[start:start+sections[s]] (cumulative over half)
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.array(sections), total_repeat_length=half)
    pos = positions[sec_id]                             # (half, B, T) gather
    pos = jnp.moveaxis(pos, 0, -1)                      # (B, T, half)
    ang = pos[:, None, :, :].astype(jnp.float32) * freqs  # (B,1,T,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (d, cfg.n_heads * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, d), dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _qkv(p: Params, x: jax.Array, cfg: ModelConfig,
         positions: jax.Array | None, mrope_positions: jax.Array | None):
    B, T, _ = x.shape
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, T, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = (x @ p["wk"]).reshape(B, T, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = (x @ p["wv"]).reshape(B, T, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if cfg.mrope_sections is not None and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.rope_theta,
                        cfg.mrope_sections)
        k = apply_mrope(k, mrope_positions, cfg.rope_theta,
                        cfg.mrope_sections)
    elif positions is not None:
        q = apply_rope(q, positions[:, None, :], cfg.rope_theta)
        k = apply_rope(k, positions[:, None, :], cfg.rope_theta)
    return q, k, v


def _sdpa_block(q, k, v, mask, scale, softcap, remask: bool = True):
    """One (bq × Tk) attention rectangle; returns (out, m, l) f32.

    remask=False skips the post-exp re-mask — one fewer full pass over the
    (bq, Tk) tile. Only safe when every query row has at least one valid
    key (causal self-attention rows always see themselves); the chunked-KV
    path keeps remask=True because whole blocks can be fully masked
    (m = −inf there would make exp(s − m) = 1, not 0)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if remask:
        p = jnp.where(mask, p, 0.0)
    l = p.sum(axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o, m, l


# Query and KV block of every splash kernel: a sequence it does not
# divide stays on the XLA branch.
SPLASH_BLOCK = 512


def _takes_kernel(cfg: ModelConfig, T: int, Tk: int, window, causal: bool,
                  kv_override) -> bool:
    """Whether a call is causal global self-attention that the splash
    kernels compute exactly, in a program XLA does not partition over
    several devices (a Mosaic kernel cannot be; shard_map bodies and
    one-device programs can run it): every other call keeps its XLA
    branch."""
    from .actsharding import mesh_ctx
    ctx = mesh_ctx()
    return (causal and isinstance(window, int) and window == 0
            and cfg.attn_softcap == 0 and kv_override is None and Tk == T
            and T % SPLASH_BLOCK == 0 and cfg.n_heads % cfg.n_kv_heads == 0
            and not cfg.attn_kv_block and (ctx is None or ctx[0].size == 1))


@functools.lru_cache(maxsize=None)
def _splash_kernel(T: int, n_heads: int, interpret: bool):
    """The causal multi-head splash kernel for T tokens, built once: its
    mask's block structure is analysed here, not at every trace."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    b = SPLASH_BLOCK
    sizes = sa.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                          block_q_dkv=b, block_kv_dkv=b,
                          block_kv_dkv_compute=b, block_q_dq=b, block_kv_dq=b)
    mask = sa.MultiHeadMask([sa.CausalMask((T, T))] * n_heads)
    with jax.ensure_compile_time_eval():    # concrete mask info, no tracers
        return sa.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                  q_seq_shards=1, interpret=interpret)


def splash_attention(q, k, v, scale: float, interpret: bool = False):
    """Causal attention through JAX's splash kernels. q: (B, H, T, D);
    k, v: (B, Hkv, T, D) with Hkv dividing H, shared by each group of H/Hkv
    query heads without a copy. bf16 operands, f32 accumulation and
    softmax statistics; the kernel takes no scale, so q carries it."""
    kernel = _splash_kernel(q.shape[2], q.shape[1], interpret)
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    return jax.vmap(kernel)(q, k, v)


def attention_path_metric(path: str) -> str:
    """The counter of traced attention calls that can take `path`
    ("kernel" or "xla"); a scanned stack of layers is one traced call."""
    return f'attention_path_total{{path="{path}"}}'


def _count_path(kernel: bool) -> None:
    from repro.runtime.metrics import default_metrics
    default_metrics().counter(
        attention_path_metric("kernel" if kernel else "xla"),
        "attention calls traced, by the path their inputs allow").inc()


def _causal_global(q, k, v, scale: float, dtype, xla):
    """The splash kernels where the program is lowered for a TPU, `xla`
    (the q-block scan) on every other platform."""
    return lax.platform_dependent(
        q, k, v, default=xla,
        tpu=lambda q, k, v: splash_attention(q, k, v, scale).astype(dtype))


def attention(p: Params, x: jax.Array, cfg: ModelConfig, *,
              window: jax.Array | int = 0, causal: bool = True,
              positions: jax.Array | None = None,
              mrope_positions: jax.Array | None = None,
              block_q: int = 512, kv_override=None) -> jax.Array:
    """Full-sequence attention (train / prefill), q-block scanned.

    window: static int (banded path when > 0) or traced scalar (masked
    path — used under scan over heterogeneous layers). Causal global
    self-attention (`_takes_kernel`) runs the splash kernels on a TPU.
    """
    B, T, _ = x.shape
    if positions is None:
        positions = jnp.arange(T)[None, :].astype(jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions, mrope_positions)
    if kv_override is not None:
        k, v = kv_override
    hd = cfg.head_dim
    scale = hd ** -0.5
    rep = cfg.n_heads // cfg.n_kv_heads
    Tk = k.shape[2]
    static_window = isinstance(window, int)
    kernel = _takes_kernel(cfg, T, Tk, window, causal, kv_override)
    _count_path(kernel)

    def repeat_kv(k, v):
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        return k, v

    if not kernel:
        k, v = repeat_kv(k, v)

    bq = min(block_q, T)
    if T % bq:
        bq = T  # fallback: single block
    nq = T // bq

    def global_scan(q, k, v):
        # ---- q-block scan against full K (global layers) -----------------
        def body(carry, qi):
            qb = lax.dynamic_slice_in_dim(q, qi * bq, bq, axis=2)
            qpos = qi * bq + jnp.arange(bq)[:, None] + (Tk - T)
            kpos = jnp.arange(Tk)[None, :]
            mask = jnp.ones((bq, Tk), bool)
            if causal:
                mask &= kpos <= qpos
            w = window
            if not static_window:
                mask &= (w <= 0) | (kpos > qpos - w)
            elif w > 0:
                mask &= kpos > qpos - w
            o, m, l = _sdpa_block(qb, k, v, mask[None, None],
                                  scale, cfg.attn_softcap,
                                  remask=not causal)
            return carry, (o / (l + 1e-30)).astype(x.dtype)

        # nested remat: without it the backward stacks each q-block's (bq, Tk)
        # probability matrix as scan residuals (9.2 TB/device measured on
        # mixtral×train_4k); recompute from (q,k,v) instead.
        _, outs = lax.scan(jax.checkpoint(body), None, jnp.arange(nq))
        return jnp.moveaxis(outs, 0, 2).reshape(B, cfg.n_heads, T, hd)

    if kernel:
        out = _causal_global(q, k, v, scale, x.dtype,
                             lambda q, k, v: global_scan(q, *repeat_kv(k, v)))
    elif static_window and window > 0 and causal and Tk == T \
            and window < T:
        # ---- banded path: each q block sees [start, start+span) of KV ----
        span = min(bq + (window // bq + 1) * bq, Tk)

        def body(carry, qi):
            start = jnp.maximum(qi * bq - (span - bq), 0)
            start = jnp.minimum(start, Tk - span)
            qb = lax.dynamic_slice_in_dim(q, qi * bq, bq, axis=2)
            kb = lax.dynamic_slice_in_dim(k, start, span, axis=2)
            vb = lax.dynamic_slice_in_dim(v, start, span, axis=2)
            qpos = qi * bq + jnp.arange(bq)[:, None]
            kpos = start + jnp.arange(span)[None, :]
            mask = (kpos <= qpos) & (kpos > qpos - window)
            o, m, l = _sdpa_block(qb, kb, vb, mask[None, None],
                                  scale, cfg.attn_softcap, remask=False)
            return carry, (o / (l + 1e-30)).astype(x.dtype)

        _, outs = lax.scan(jax.checkpoint(body), None, jnp.arange(nq))
        # outs: (nq, B, H, bq, hd) → (B, H, T, hd)
        out = jnp.moveaxis(outs, 0, 2).reshape(B, cfg.n_heads, T, hd)
    elif cfg.attn_kv_block and Tk % cfg.attn_kv_block == 0 \
            and cfg.attn_kv_block < Tk:
        # ---- flash-in-XLA: online-softmax scan over KV blocks -------------
        # Materializes only (bq × bk) logit tiles + running (m, l, acc)
        # accumulators, instead of the full (bq × Tk) rectangle — the same
        # algorithm the Pallas kernel runs in VMEM, expressed in XLA ops so
        # the HBM traffic shrinks on every backend.
        bk = cfg.attn_kv_block
        nk = Tk // bk

        def q_body(carry, qi):
            qb = lax.dynamic_slice_in_dim(q, qi * bq, bq, axis=2)
            qpos = qi * bq + jnp.arange(bq)[:, None] + (Tk - T)

            def kv_body(acc, ki):
                o_acc, m_acc, l_acc = acc
                kb = lax.dynamic_slice_in_dim(k, ki * bk, bk, axis=2)
                vb = lax.dynamic_slice_in_dim(v, ki * bk, bk, axis=2)
                kpos = ki * bk + jnp.arange(bk)[None, :]
                mask = jnp.ones((bq, bk), bool)
                if causal:
                    mask &= kpos <= qpos
                w = window
                if not static_window:
                    mask &= (w <= 0) | (kpos > qpos - w)
                elif w > 0:
                    mask &= kpos > qpos - w
                o, m, l = _sdpa_block(qb, kb, vb, mask[None, None],
                                      scale, cfg.attn_softcap)
                m_new = jnp.maximum(m_acc, m)
                alpha = jnp.exp(m_acc - m_new)
                beta = jnp.exp(m - m_new)
                return (o_acc * alpha + o * beta,
                        m_new, l_acc * alpha + l * beta), None

            o0 = jnp.zeros((B, cfg.n_heads, bq, hd), jnp.float32)
            m0 = jnp.full((B, cfg.n_heads, bq, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, cfg.n_heads, bq, 1), jnp.float32)
            (o, _m, l), _ = lax.scan(kv_body, (o0, m0, l0), jnp.arange(nk))
            return carry, (o / (l + 1e-30)).astype(x.dtype)

        _, outs = lax.scan(jax.checkpoint(q_body), None, jnp.arange(nq))
        out = jnp.moveaxis(outs, 0, 2).reshape(B, cfg.n_heads, T, hd)
    else:
        out = global_scan(q, k, v)

    out = out.transpose(0, 2, 1, 3).reshape(B, T, cfg.n_heads * hd)
    return out @ p["wo"]


def attention_decode(p: Params, x: jax.Array, cache_k: jax.Array,
                     cache_v: jax.Array, pos: jax.Array, cfg: ModelConfig, *,
                     window: jax.Array | int = 0,
                     mrope_positions: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode. x: (B, 1, D); cache_{k,v}: (B, Hkv, S, hd);
    pos: (B,) current write position. Returns (out, new_k, new_v)."""
    B = x.shape[0]
    hd = cfg.head_dim
    positions = pos[:, None].astype(jnp.int32)
    q, k_new, v_new = _qkv(p, x, cfg, positions, mrope_positions)
    # scatter the new K/V at `pos` along the seq axis, per batch element
    ck = jax.vmap(
        lambda c, kn, i: lax.dynamic_update_slice_in_dim(c, kn, i, axis=1)
    )(cache_k, k_new, pos)
    cv = jax.vmap(
        lambda c, vn, i: lax.dynamic_update_slice_in_dim(c, vn, i, axis=1)
    )(cache_v, v_new, pos)

    rep = cfg.n_heads // cfg.n_kv_heads
    k = jnp.repeat(ck, rep, axis=1) if rep > 1 else ck
    v = jnp.repeat(cv, rep, axis=1) if rep > 1 else cv
    S = k.shape[2]
    kpos = jnp.arange(S)[None, :]                       # (1, S)
    valid = kpos <= pos[:, None]
    w = window
    if isinstance(w, int):
        if w > 0:
            valid &= kpos > pos[:, None] - w
    else:
        valid &= (w <= 0) | (kpos > pos[:, None] - w)
    mask = valid[:, None, None, :]                      # (B,1,1,S)
    o, m, l = _sdpa_block(q, k, v, mask, hd ** -0.5, cfg.attn_softcap)
    out = (o / (l + 1e-30)).astype(x.dtype)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, cfg.n_heads * hd)
    return out @ p["wo"], ck, cv


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------
def init_mlp(key, d: int, f: int, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 3)
    return {"wi": dense_init(ks[0], (d, f), dtype=dtype),
            "wg": dense_init(ks[1], (d, f), dtype=dtype),
            "wo": dense_init(ks[2], (f, d), dtype=dtype)}


def mlp(p: Params, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------
def init_moe(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, fe = cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, cfg.n_experts), scale=0.02,
                             dtype=jnp.float32),
        "wi": dense_init(ks[1], (cfg.n_experts, d, fe), dtype=dtype),
        "wg": dense_init(ks[2], (cfg.n_experts, d, fe), dtype=dtype),
        "wo": dense_init(ks[3], (cfg.n_experts, fe, d), dtype=dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, fe * cfg.n_shared_experts, dtype)
    return p


def _moe_sorted_block(xt, topi, topv, p, E: int, k: int, D: int,
                      capacity_factor: float) -> jax.Array:
    """Capacity-bounded sort-based dispatch over ONE token block.
    Combine is gather-based (scatter-add onto the token tensor defeats
    SPMD — the output replicates and all-reduces)."""
    n = xt.shape[0]
    cap = int(n * k * capacity_factor / E) + 1
    cap = max(8, -(-cap // 8) * 8)                       # round up to 8
    e_flat = topi.reshape(-1)                            # (n·k,)
    order = jnp.argsort(e_flat)
    sorted_e = e_flat[order]
    counts = jnp.bincount(e_flat, length=E)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(n * k) - starts[sorted_e]
    keep = rank < cap
    buf_idx = jnp.where(keep, sorted_e * cap + rank, E * cap)  # spill row
    tok_idx = order // k
    buf = jnp.zeros((E * cap + 1, D), xt.dtype)
    buf = buf.at[buf_idx].set(xt[tok_idx], mode="drop")
    eb = buf[: E * cap].reshape(E, cap, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", eb, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", eb, p["wi"])
    y = jnp.einsum("ecf,efd->ecd", h, p["wo"]).reshape(E * cap, D)
    inv = jnp.argsort(order)                             # (n·k,)
    slot_buf = jnp.take(buf_idx, inv)
    slot_keep = jnp.take(keep, inv)
    rows = jnp.take(y, jnp.minimum(slot_buf, E * cap - 1), axis=0)
    rows = jnp.where(slot_keep[:, None], rows.astype(jnp.float32), 0.0)
    return jnp.einsum("nkd,nk->nd", rows.reshape(n, k, D),
                      topv.astype(jnp.float32))


def _moe_sorted_block_ns(xt, topi, topv, p, E: int, k: int, D: int,
                         capacity_factor: float) -> jax.Array:
    """Scatter-free sorted dispatch (one token block).

    GSPMD replicates `scatter` ops with data-dependent indices — under
    vmap over DP-sharded groups the whole expert buffer ends up on every
    device. This formulation uses only sort_key_val / cumsum / gather,
    all of which GSPMD shards along batch dims:

      sort (expert_id, slot_id) → per-expert contiguous runs;
      buf[e, c] = x[token_of(run position starts[e] + c)]   (gather)
      combine: slot j reads y[buf_pos(j)]                    (gather)
    """
    n = xt.shape[0]
    cap = int(n * k * capacity_factor / E) + 1
    cap = max(8, -(-cap // 8) * 8)
    nk_ = n * k
    e_flat = topi.reshape(-1).astype(jnp.int32)           # (n·k,)
    slot = jnp.arange(nk_, dtype=jnp.int32)
    sorted_e, sorted_slot = lax.sort_key_val(e_flat, slot)
    counts = (jax.nn.one_hot(e_flat, E, dtype=jnp.int32)).sum(0)   # (E,)
    starts = jnp.cumsum(counts) - counts                  # (E,)
    # rank of each sorted position within its expert run
    rank = jnp.arange(nk_, dtype=jnp.int32) - starts[sorted_e]
    # token filling buffer cell (e, c): sorted position starts[e] + c
    pos = starts[:, None] + jnp.arange(cap, dtype=jnp.int32)[None]  # (E,cap)
    valid = jnp.arange(cap, dtype=jnp.int32)[None] < counts[:, None]
    pos = jnp.clip(pos, 0, nk_ - 1)
    tok_for_cell = jnp.take(sorted_slot, pos.reshape(-1)) // k      # (E·cap,)
    eb = jnp.take(xt, tok_for_cell, axis=0).reshape(E, cap, D)
    eb = jnp.where(valid.reshape(E, cap)[..., None], eb, 0)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", eb, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", eb, p["wi"])
    y = jnp.einsum("ecf,efd->ecd", h, p["wo"]).reshape(E * cap, D)
    # inverse: original slot j sits at sorted position inv[j]
    _, inv = lax.sort_key_val(sorted_slot,
                              jnp.arange(nk_, dtype=jnp.int32))
    rank_of_slot = jnp.take(rank, inv)                    # (n·k,)
    e_of_slot = e_flat
    keep = rank_of_slot < cap
    buf_pos = jnp.clip(e_of_slot * cap + rank_of_slot, 0, E * cap - 1)
    rows = jnp.take(y, buf_pos, axis=0)
    rows = jnp.where(keep[:, None], rows.astype(jnp.float32), 0.0)
    return jnp.einsum("nkd,nk->nd", rows.reshape(n, k, D),
                      topv.astype(jnp.float32))


def _moe_local_shardmap(p, xt, topi, topv, cfg, E, k, D,
                        capacity_factor) -> jax.Array:
    """Device-local MoE dispatch (DeepSpeed-style): a shard_map region
    over the DP axes keeps sort/scatter/combine local per data shard —
    GSPMD otherwise replicates the expert buffers (the global argsort is
    unpartitionable: measured 22 GB/layer of tuple all-reduce on
    mixtral×train_4k). Expert weights are ZeRO-gathered over DP
    explicitly (the cheap collective: ~300 MB/layer/device vs 22 GB);
    the 'model' axis stays auto so Fe keeps its TP sharding."""
    from jax.sharding import PartitionSpec as P
    from . import actsharding
    ctx = actsharding.mesh_ctx()
    n = xt.shape[0]
    if ctx is None:
        return _moe_sorted_block(xt, topi, topv, p, E, k, D,
                                 capacity_factor)
    mesh, dp = ctx
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dpn = 1
    for a in dp:
        dpn *= sizes[a]
    if n % dpn or n == dpn:
        return _moe_sorted_block(xt, topi, topv, p, E, k, D,
                                 capacity_factor)

    # dp-sharded axis of each weight leaf, from the same rule the
    # launcher sharded the stacked (L, ...) params with
    from repro.launch.sharding import leaf_spec

    def dp_spec(leaf):
        full = leaf_spec((1,) + leaf.shape, mesh)   # stacked-layout rule
        entries = list(full)[1:]
        return P(*[e if e in ("data", "pod") or isinstance(e, tuple)
                   else None for e in entries])

    w_specs = jax.tree.map(dp_spec, p)

    def local(w, xt_l, ti_l, tv_l):
        # ZeRO gather: undo the dp sharding of each weight leaf
        def gather(wl, spec):
            for ax, name in enumerate(spec):
                if name is None:
                    continue
                names = name if isinstance(name, tuple) else (name,)
                for nm in names:
                    wl = jax.lax.all_gather(wl, nm, axis=ax, tiled=True)
            return wl

        w = jax.tree.map(gather, w, w_specs,
                         is_leaf=lambda x: hasattr(x, "shape"))
        return _moe_sorted_block(xt_l, ti_l, tv_l, w, E, k, D,
                                 capacity_factor)

    from repro.core.compat import shard_map
    return shard_map(
        local,
        mesh=mesh,
        in_specs=(w_specs, P(dp, None), P(dp, None), P(dp, None)),
        out_specs=P(dp, None),
        axis_names=set(dp),
        check_vma=False,
    )(p, xt, topi, topv)


def _moe_ep_block(xt, topi, topv, wi, wg, wo, ndev: int, E: int, k: int,
                  D: int, capacity_factor: float, a2a) -> jax.Array:
    """One device's expert-parallel dispatch (runs inside shard_map).

    Local tokens pack into the per-GLOBAL-expert capacity buffer (same
    sort-based pack as `_moe_sorted_block`), the buffer's per-owner
    chunks AllToAll to the expert owners, each owner runs its E/ndev
    local experts (weights `wi`/`wg`/`wo` are the LOCAL slices), and the
    outputs AllToAll back into the original buffer layout for the
    gather-based combine. `a2a` is the exchange callable — planned
    schedule or lax.all_to_all via `core.sync.ep_all_to_all`."""
    n = xt.shape[0]
    e_local = E // ndev
    cap = int(n * k * capacity_factor / E) + 1
    cap = max(8, -(-cap // 8) * 8)                       # round up to 8
    e_flat = topi.reshape(-1)                            # (n·k,)
    order = jnp.argsort(e_flat)
    sorted_e = e_flat[order]
    counts = jnp.bincount(e_flat, length=E)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(n * k) - starts[sorted_e]
    keep = rank < cap
    buf_idx = jnp.where(keep, sorted_e * cap + rank, E * cap)  # spill row
    tok_idx = order // k
    buf = jnp.zeros((E * cap + 1, D), xt.dtype)
    buf = buf.at[buf_idx].set(xt[tok_idx], mode="drop")
    # owner-major: row j = my capacity rows for owner j's expert group
    send = buf[: E * cap].reshape(ndev, e_local * cap * D)
    recv = a2a(send)                  # row s = device s's rows for ME
    eb = recv.reshape(ndev, e_local, cap, D).transpose(1, 0, 2, 3) \
             .reshape(e_local, ndev * cap, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", eb, wg)) * \
        jnp.einsum("ecd,edf->ecf", eb, wi)
    y = jnp.einsum("ecf,efd->ecd", h, wo)                # (e_local, n·cap, D)
    back = y.reshape(e_local, ndev, cap, D).transpose(1, 0, 2, 3) \
            .reshape(ndev, e_local * cap * D)
    got = a2a(back).reshape(E * cap, D)   # my buffer layout, expert outputs
    inv = jnp.argsort(order)
    slot_buf = jnp.take(buf_idx, inv)
    slot_keep = jnp.take(keep, inv)
    rows = jnp.take(got, jnp.minimum(slot_buf, E * cap - 1), axis=0)
    rows = jnp.where(slot_keep[:, None], rows.astype(jnp.float32), 0.0)
    return jnp.einsum("nkd,nk->nd", rows.reshape(n, k, D),
                      topv.astype(jnp.float32))


def _moe_ep(p, xt, topi, topv, cfg, E, k, D, capacity_factor) -> jax.Array:
    """Expert-parallel MoE dispatch (the planned-AllToAll path, ISSUE 9).

    Two entry contexts:
      * inside the manual trainer's shard_map — `core.sync.ep_context()`
        is set: params are the ZeRO-gathered FULL weights, so each device
        slices its expert group by axis index and exchanges over the
        context's axis (planned schedule when the context carries one);
      * under GSPMD jit — wraps a shard_map over the single live DP axis
        with the expert dim sharded in-spec.
    Falls back to the sorted/local paths when the expert count doesn't
    shard evenly or the mesh shape doesn't fit."""
    from repro.core import sync as _sync
    ep = _sync.ep_context()
    if ep is not None:
        if ep.size <= 1 or E % ep.size:
            return _moe_sorted_block(xt, topi, topv, p, E, k, D,
                                     capacity_factor)
        e_local = E // ep.size
        idx = lax.axis_index(ep.axis)

        def sl(w):
            return lax.dynamic_slice_in_dim(w, idx * e_local, e_local, 0)

        return _moe_ep_block(xt, topi, topv, sl(p["wi"]), sl(p["wg"]),
                             sl(p["wo"]), ep.size, E, k, D,
                             capacity_factor,
                             lambda v: _sync.ep_all_to_all(v, ep.axis))
    from jax.sharding import PartitionSpec as P
    from . import actsharding
    ctx = actsharding.mesh_ctx()
    n = xt.shape[0]
    if ctx is None:
        return _moe_sorted_block(xt, topi, topv, p, E, k, D,
                                 capacity_factor)
    mesh, dp = ctx
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    live = [a for a in dp if sizes[a] > 1]
    if len(live) != 1 or E % sizes[live[0]] or n % sizes[live[0]] \
            or n == sizes[live[0]]:
        return _moe_local_shardmap(p, xt, topi, topv, cfg, E, k, D,
                                   capacity_factor)
    axis, ndev = live[0], sizes[live[0]]

    def local(wi, wg, wo, xt_l, ti_l, tv_l):
        from repro.core import sync as _s
        return _moe_ep_block(xt_l, ti_l, tv_l, wi, wg, wo, ndev, E, k, D,
                             capacity_factor,
                             lambda v: _s.ep_all_to_all(v, axis))

    from repro.core.compat import shard_map
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis),
                  P(axis, None), P(axis, None), P(axis, None)),
        out_specs=P(axis, None),
        axis_names={axis},
        check_vma=False,
    )(p["wi"], p["wg"], p["wo"], xt, topi, topv)


def moe(p: Params, x: jax.Array, cfg: ModelConfig, *,
        dispatch: str = "sorted", capacity_factor: float = 1.25
        ) -> jax.Array:
    """x: (B, T, D). dispatch: "sorted" (capacity-bounded sort-based pack,
    FLOPs ≈ active-expert FLOPs × capacity factor), "dense" (computes all
    experts everywhere and masks — robust but E/top_k × wasteful; kept as
    the hillclimb baseline), or "ep" (expert-parallel: the sorted pack's
    capacity buffer exchanged owner-major over `sync.ep_all_to_all` so
    each device computes only its expert shard — DESIGN.md §14; falls
    back to the local sorted block when no EP context / mesh fits).

    cfg.moe_groups > 0 blocks the dispatch into G groups sorted
    independently (per-group capacity): a global argsort over the sharded
    token axis forces XLA to replicate the expert buffers on every device
    (measured: 22 GB of tuple all-reduce per mixtral layer); per-group
    sort keeps buffers DP-local."""
    B, T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(B * T, D)
    n = B * T
    logits = (xt.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, k)                     # (n, k)
    topv = topv / (topv.sum(-1, keepdims=True) + 1e-9)

    if dispatch == "dense":
        # gate (n, E) with only top-k nonzero
        gate = jnp.zeros((n, E), jnp.float32).at[
            jnp.arange(n)[:, None], topi].set(topv)
        h = jnp.einsum("nd,edf->nef", xt, p["wg"])
        h = jax.nn.silu(h) * jnp.einsum("nd,edf->nef", xt, p["wi"])
        y = jnp.einsum("nef,efd->ned", h, p["wo"])
        out = jnp.einsum("ned,ne->nd", y.astype(jnp.float32), gate)
    elif dispatch == "ep":
        out = _moe_ep(p, xt, topi, topv, cfg, E, k, D, capacity_factor)
    elif dispatch == "local" or (dispatch == "sorted" and cfg.moe_local):
        out = _moe_local_shardmap(p, xt, topi, topv, cfg, E, k, D,
                                  capacity_factor)
    elif cfg.moe_groups > 1 and n % cfg.moe_groups == 0:
        G = cfg.moe_groups
        ng = n // G
        # FSDP gather-before-use: re-shard the expert weights so the
        # contracted d axis is NOT 'data'-sharded — otherwise GSPMD picks
        # the partial-sum plan and all-reduces (E, cap, f) activations
        # (~22 GB/layer on mixtral) instead of gathering ~300 MB of
        # weights. The constraint makes the cheap plan the only plan.
        from .actsharding import mesh_ctx
        ctx = mesh_ctx()
        pw = p
        if ctx is not None:
            mesh, _dp = ctx
            from jax.sharding import NamedSharding, PartitionSpec as SP
            model = dict(zip(mesh.axis_names,
                             mesh.devices.shape)).get("model", 1)

            def unfsdp(w, f_axis):
                spec = [None] * w.ndim
                if model > 1 and w.shape[f_axis] % model == 0:
                    spec[f_axis] = "model"
                return jax.lax.with_sharding_constraint(
                    w, NamedSharding(mesh, SP(*spec)))

            pw = dict(p)
            pw["wi"] = unfsdp(p["wi"], 2)     # (E, D, Fe) — Fe on model
            pw["wg"] = unfsdp(p["wg"], 2)
            pw["wo"] = unfsdp(p["wo"], 1)     # (E, Fe, D) — Fe on model
        out = jax.vmap(
            lambda xg, ig, vg: _moe_sorted_block_ns(
                xg, ig, vg, pw, E, k, D, capacity_factor)
        )(xt.reshape(G, ng, D), topi.reshape(G, ng, k),
          topv.reshape(G, ng, k)).reshape(n, D)
    else:
        out = _moe_sorted_block(xt, topi, topv, p, E, k, D,
                                capacity_factor)

    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], xt).astype(jnp.float32)
    return out.astype(x.dtype).reshape(B, T, D)
