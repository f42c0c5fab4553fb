"""Unified decoder-only transformer covering the dense / moe / vlm families.

Layers are *stacked*: every parameter leaf carries a leading (L,) axis and
the forward pass is one `lax.scan` over layers (fast lowering at 64 layers,
uniform sharding). Per-layer heterogeneity (sliding windows in gemma-2/3,
hymba's global layers) rides along as an (L,) int array scanned with the
params, using the masked-window attention path.

Three entry points per model:
  * ``forward``       — full-sequence logits (training / prefill math)
  * ``prefill``       — forward + returns the populated KV cache
  * ``decode_step``   — one new token against a KV cache

The KV cache layout is (L, B, Hkv, S, hd) so the sequence axis is shardable
for long contexts and the layer axis matches the scanned params.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.runtime.trace import scope

from .actsharding import constrain
from .config import ModelConfig
from .layers import (Params, attention, attention_decode, dense_init,
                     init_attention, init_mlp, init_moe, mlp, moe, rmsnorm)

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    """Stacked-layer parameter pytree."""
    L = cfg.n_layers
    keys = jax.random.split(key, L + 2)

    def layer(k) -> Params:
        ks = jax.random.split(k, 4)
        p: Params = {
            "ln1": jnp.zeros((cfg.d_model,), dtype),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
            "attn": init_attention(ks[0], cfg, dtype),
        }
        if cfg.n_experts:
            p["moe"] = init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
        return p

    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[layer(keys[i]) for i in range(L)])
    p: Params = {
        "layers": stacked,
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
        "embed": dense_init(keys[L], (cfg.vocab, cfg.d_model), scale=0.02,
                            dtype=dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[L + 1], (cfg.d_model, cfg.vocab),
                                  dtype=dtype)
    return p


def window_array(cfg: ModelConfig) -> jnp.ndarray:
    return jnp.array([cfg.window_for_layer(i) for i in range(cfg.n_layers)],
                     jnp.int32)


def _uniform_windows(cfg: ModelConfig) -> bool:
    ws = {cfg.window_for_layer(i) for i in range(cfg.n_layers)}
    return len(ws) == 1


def _grouped_layer_scan(layers: Params, cfg: ModelConfig, x, group_fn,
                        remat: bool = True):
    """Scan over pattern-period groups of layers (static windows inside);
    leftover layers (L % period) run unrolled at the end."""
    L, period = cfg.n_layers, len(cfg.window_pattern)
    full = (L // period) * period

    if full:
        grouped = jax.tree.map(
            lambda a: a[:full].reshape((full // period, period)
                                       + a.shape[1:]), layers)

        def body(x, lp_group):
            return group_fn(x, lp_group, range(period)), None

        blk = jax.checkpoint(body) if remat else body
        x, _ = lax.scan(blk, x, grouped)
    if full < L:
        tail = jax.tree.map(lambda a: a[full:], layers)
        fn = (jax.checkpoint(lambda x, t: group_fn(x, t, range(L - full)))
              if remat else (lambda x, t: group_fn(x, t, range(L - full))))
        x = fn(x, tail)
    return x


# ---------------------------------------------------------------------------
# forward (train / prefill math)
# ---------------------------------------------------------------------------
def _block(cfg: ModelConfig, lp: Params, x, window, positions,
           mrope_positions, moe_dispatch: str):
    with scope("model/attn"):
        h = attention(lp["attn"], rmsnorm(x, lp["ln1"]), cfg, window=window,
                      positions=positions, mrope_positions=mrope_positions)
    x = x + h
    with scope("model/mlp"):
        z = rmsnorm(x, lp["ln2"])
        if cfg.n_experts:
            f = moe(lp["moe"], z, cfg, dispatch=moe_dispatch)
        else:
            f = mlp(lp["mlp"], z)
    return x + f


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array | None, *,
            embeds: jax.Array | None = None,
            positions: jax.Array | None = None,
            mrope_positions: jax.Array | None = None,
            moe_dispatch: str = "sorted",
            remat: bool = True) -> jax.Array:
    """tokens (B, T) int32 or embeds (B, T, D) → logits (B, T, V)."""
    if embeds is None:
        with scope("model/embed"):
            x = jnp.take(params["embed"], tokens, axis=0)
            if cfg.family == "dense" and cfg.tie_embeddings:
                x = x * (cfg.d_model ** 0.5)
    else:
        x = embeds
    B, T, _ = x.shape
    if positions is None:
        positions = jnp.arange(T, dtype=jnp.int32)[None, :]

    wins = window_array(cfg)
    static_win = cfg.window_for_layer(0) if _uniform_windows(cfg) else None

    if static_win is None:
        # Heterogeneous windows: scan over pattern-period layer GROUPS so
        # every sub-layer gets a STATIC window — the banded O(T·W)
        # attention path applies to local layers. A traced per-layer
        # window forces the masked O(T²) path for the whole stack
        # (EXPERIMENTS.md §Perf iter 10: gemma2 prefill 178 s → banded).
        def group_fn(x, lp_group, js):
            for j in js:
                lpj = jax.tree.map(lambda a, j=j: a[j], lp_group)
                x = constrain(_block(cfg, lpj, x, cfg.window_for_layer(j),
                                     positions, mrope_positions,
                                     moe_dispatch))
            return x

        with scope("model/layers"):
            x = _grouped_layer_scan(params["layers"], cfg, x, group_fn,
                                    remat=remat)
    else:
        def body(x, inp):
            lp, _w = inp
            return constrain(_block(cfg, lp, x, static_win, positions,
                                    mrope_positions, moe_dispatch)), None

        blk = jax.checkpoint(body) if remat else body
        with scope("model/layers"):
            x, _ = lax.scan(blk, x, (params["layers"], wins))
    with scope("model/head"):
        x = rmsnorm(x, params["ln_f"])
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x @ head
        if cfg.final_softcap > 0:
            logits = (jnp.tanh(logits.astype(jnp.float32) / cfg.final_softcap)
                      * cfg.final_softcap).astype(logits.dtype)
    return logits


def loss_fn(params: Params, cfg: ModelConfig, batch: dict, **kw) -> jax.Array:
    logits = forward(params, cfg, batch.get("tokens"),
                     embeds=batch.get("embeds"),
                     mrope_positions=batch.get("mrope_positions"), **kw)
    labels = batch["labels"]
    with scope("model/head"):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
        mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, seq: int,
               dtype=jnp.bfloat16) -> dict:
    """Sliding-window layers only need min(window, seq) cache slots; the
    cache is allocated at the max over layers so the scanned layout stays
    rectangular (per-layer ragged caches don't scan)."""
    hd = cfg.head_dim
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, seq, hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array | None, *,
            cache_len: int, embeds: jax.Array | None = None,
            mrope_positions: jax.Array | None = None,
            moe_dispatch: str = "sorted") -> tuple[jax.Array, dict]:
    """Forward over the prompt, recording K/V into a fresh cache of
    `cache_len` slots. Returns (last-token logits, cache)."""
    if embeds is None:
        x = jnp.take(params["embed"], tokens, axis=0)
    else:
        x = embeds
    B, T, _ = x.shape
    positions = jnp.arange(T, dtype=jnp.int32)[None, :]
    wins = window_array(cfg)
    static_win = cfg.window_for_layer(0) if _uniform_windows(cfg) else None
    hd = cfg.head_dim

    def one_layer(x, lp, win):
        z = rmsnorm(x, lp["ln1"])
        # recompute K/V for the cache (attention() also derives them; the
        # double projection is optimized away by CSE)
        from .layers import _qkv
        _, k, v, = _qkv(lp["attn"], z, cfg, positions, mrope_positions)
        h = attention(lp["attn"], z, cfg, window=win, positions=positions,
                      mrope_positions=mrope_positions)
        x = x + h
        zz = rmsnorm(x, lp["ln2"])
        f = moe(lp["moe"], zz, cfg, dispatch=moe_dispatch) if cfg.n_experts \
            else mlp(lp["mlp"], zz)
        return constrain(x + f), k, v

    if static_win is None:
        # pattern-period grouping: static window per sub-layer (see forward)
        def group_fn(x, lp_group, js):
            ks_, vs_ = [], []
            for j in js:
                lpj = jax.tree.map(lambda a, j=j: a[j], lp_group)
                x, k, v = one_layer(x, lpj, cfg.window_for_layer(j))
                ks_.append(k)
                vs_.append(v)
            return x, (jnp.stack(ks_), jnp.stack(vs_))

        L, period = cfg.n_layers, len(cfg.window_pattern)
        full = (L // period) * period
        parts_k, parts_v = [], []
        if full:
            grouped = jax.tree.map(
                lambda a: a[:full].reshape((full // period, period)
                                           + a.shape[1:]),
                params["layers"])

            def body2(x, lp_group):
                x, kv = group_fn(x, lp_group, range(period))
                return x, kv

            x, (gk, gv) = lax.scan(jax.checkpoint(body2), x, grouped)
            parts_k.append(gk.reshape((full,) + gk.shape[2:]))
            parts_v.append(gv.reshape((full,) + gv.shape[2:]))
        if full < L:
            tail = jax.tree.map(lambda a: a[full:], params["layers"])
            x, (tk, tv) = jax.checkpoint(
                lambda x, t: group_fn(x, t, range(L - full)))(x, tail)
            parts_k.append(tk)
            parts_v.append(tv)
        ks = jnp.concatenate(parts_k, axis=0)
        vs = jnp.concatenate(parts_v, axis=0)
    else:
        def body(x, inp):
            lp, _w = inp
            x, k, v = one_layer(x, lp, static_win)
            return x, (k, v)

        x, (ks, vs) = lax.scan(jax.checkpoint(body), x,
                               (params["layers"], wins))
    x = rmsnorm(x, params["ln_f"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x[:, -1:] @ head
    if cfg.final_softcap > 0:
        logits = (jnp.tanh(logits.astype(jnp.float32) / cfg.final_softcap)
                  * cfg.final_softcap).astype(logits.dtype)
    cache = init_cache(cfg, B, cache_len, ks.dtype)
    cache["k"] = lax.dynamic_update_slice(
        cache["k"], ks, (0, 0, 0, 0, 0))
    cache["v"] = lax.dynamic_update_slice(
        cache["v"], vs, (0, 0, 0, 0, 0))
    cache["pos"] = jnp.full((B,), T, jnp.int32)
    return logits, cache


def decode_step(params: Params, cfg: ModelConfig, cache: dict,
                tokens: jax.Array | None, *,
                embeds: jax.Array | None = None,
                mrope_positions: jax.Array | None = None,
                moe_dispatch: str = "sorted") -> tuple[jax.Array, dict]:
    """One decode step. tokens: (B, 1) (or embeds (B, 1, D)).
    Returns (logits (B, 1, V), updated cache)."""
    if embeds is None:
        x = jnp.take(params["embed"], tokens, axis=0)
    else:
        x = embeds
    B = x.shape[0]
    pos = cache["pos"]
    wins = window_array(cfg)
    static_win = cfg.window_for_layer(0) if _uniform_windows(cfg) else None

    def body(x, inp):
        lp, w, ck, cv = inp
        win = static_win if static_win is not None else w
        z = rmsnorm(x, lp["ln1"])
        h, nk, nv = attention_decode(lp["attn"], z, ck, cv, pos, cfg,
                                     window=win,
                                     mrope_positions=mrope_positions)
        x = x + h
        zz = rmsnorm(x, lp["ln2"])
        f = moe(lp["moe"], zz, cfg, dispatch=moe_dispatch) if cfg.n_experts \
            else mlp(lp["mlp"], zz)
        return constrain(x + f), (nk, nv)

    x, (nks, nvs) = lax.scan(body, x, (params["layers"], wins,
                                       cache["k"], cache["v"]))
    x = rmsnorm(x, params["ln_f"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    if cfg.final_softcap > 0:
        logits = (jnp.tanh(logits.astype(jnp.float32) / cfg.final_softcap)
                  * cfg.final_softcap).astype(logits.dtype)
    new_cache = {"k": nks, "v": nvs, "pos": pos + 1}
    return logits, new_cache
