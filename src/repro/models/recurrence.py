"""Linear-recurrence layers: RWKV6 (Finch) time-mix and Mamba-style
selective SSM (Hymba's parallel branch).

RWKV6 uses a *chunked parallel* form: within a chunk of C tokens the pair
weight for (t, s<t) is exp(Λ_t − Λ_s) per channel with Λ the running
log-decay sum — every exponent is ≤ 0, so the form is unconditionally
numerically stable (no 1/decay blow-ups). Cross-chunk state is carried by
lax.scan. The (C, C, K) pair tensor is the compute hot-spot a Mosaic kernel
would fuse on real TPU; the XLA form lowers everywhere and has the right
FLOP count.

Mamba's decay is per (channel, state) — not separable — so Hymba's SSM
branch runs a chunk-checkpointed sequential scan (outer scan saves one
carry per chunk; the inner steps are rematerialized in backward), keeping
activation memory at T/C × state instead of T × state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig
from .layers import Params, dense_init, rmsnorm


# ---------------------------------------------------------------------------
# RWKV6
# ---------------------------------------------------------------------------
def init_rwkv(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d = cfg.d_model
    h, hd = cfg.n_heads, cfg.head_dim
    lora = 64
    ks = jax.random.split(key, 12)
    return {
        "mu": (jax.random.uniform(ks[0], (5, d), jnp.float32)).astype(dtype),
        "wr": dense_init(ks[1], (d, h * hd), dtype=dtype),
        "wk": dense_init(ks[2], (d, h * hd), dtype=dtype),
        "wv": dense_init(ks[3], (d, h * hd), dtype=dtype),
        "wg": dense_init(ks[4], (d, h * hd), dtype=dtype),
        "wo": dense_init(ks[5], (h * hd, d), dtype=dtype),
        "w0": (jax.random.normal(ks[6], (h * hd,), jnp.float32) * 0.5
               - 2.0).astype(jnp.float32),
        "w_a": dense_init(ks[7], (d, lora), dtype=dtype),
        "w_b": dense_init(ks[8], (lora, h * hd), scale=0.01, dtype=dtype),
        "u": (jax.random.normal(ks[9], (h, hd), jnp.float32) * 0.1
              ).astype(jnp.float32),
        "ln_x": jnp.zeros((h * hd,), dtype),
        # channel mix
        "cm_mu": jax.random.uniform(ks[10], (2, d), jnp.float32).astype(dtype),
        "cm_k": dense_init(ks[11], (d, cfg.d_ff), dtype=dtype),
        "cm_v": dense_init(jax.random.fold_in(key, 99), (cfg.d_ff, d),
                           dtype=dtype),
        "cm_r": dense_init(jax.random.fold_in(key, 98), (d, d), dtype=dtype),
    }


def _token_shift(x: jax.Array, prev: jax.Array | None = None) -> jax.Array:
    """x: (B, T, D) → x shifted right by one (first slot = prev or 0)."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _wkv_chunk(r, k, v, logw, u, s0, chunk: int, remat: bool = False):
    """Chunked WKV. r/k/v: (B,H,T,K|V); logw: (B,H,T,K) ≤ 0;
    u: (H,K); s0: (B,H,K,V). Returns (out (B,H,T,V), s_final).
    With `remat` the backward pass recomputes each chunk, so its
    residuals are one state per chunk instead of the (C, C, K) pair
    tensors."""
    B, H, T, K = k.shape
    V = v.shape[-1]
    C = min(chunk, T)
    while T % C:          # largest divisor of T not exceeding `chunk`
        C -= 1
    nc = T // C

    def body(s, inputs):
        rc, kc, vc, lw = inputs                    # (B,H,C,·)
        linc = jnp.cumsum(lw, axis=2)              # inclusive Λ (B,H,C,K)
        lexc = linc - lw                           # exclusive
        # state contribution
        o1 = jnp.einsum("bhtk,bhkv->bhtv", rc * jnp.exp(lexc), s)
        # intra-chunk pairs (s < t): exponent lexc_t − linc_s ≤ 0
        expo = lexc[:, :, :, None, :] - linc[:, :, None, :, :]  # (B,H,C,C,K)
        tmask = (jnp.arange(C)[:, None] > jnp.arange(C)[None, :])
        expo = jnp.where(tmask[None, None, :, :, None], expo, -jnp.inf)
        pair = jnp.exp(expo)
        att = jnp.einsum("bhtk,bhsk,bhtsk->bhts", rc, kc, pair)
        o2 = jnp.einsum("bhts,bhsv->bhtv", att, vc)
        # bonus (current token)
        bonus = jnp.einsum("bhtk,bhtk->bht", rc, kc * u[None, :, None, :])
        o3 = bonus[..., None] * vc
        # state update
        ltot = linc[:, :, -1:, :]                  # (B,H,1,K)
        s_new = jnp.exp(ltot.squeeze(2))[..., None] * s + jnp.einsum(
            "bhtk,bhtv->bhkv", kc * jnp.exp(ltot - linc), vc)
        return s_new, o1 + o2 + o3

    def split(a):
        return a.reshape(B, H, nc, C, a.shape[-1]).transpose(2, 0, 1, 3, 4)

    s_fin, outs = lax.scan(
        jax.checkpoint(body) if remat else body, s0,
        (split(r), split(k), split(v), split(logw)))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(B, H, T, V)
    return out, s_fin


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def wkv_mosaic(r, k, v, logw, u, s0, chunk: int, interpret: bool = False):
    """The Pallas WKV kernel (kernels/wkv.py) with a backward pass: the
    kernel computes the forward; the gradient is XLA's derivative of the
    same chunked recurrence (`_wkv_chunk`), as no backward kernel exists.
    That derivative evaluates the recurrence again, chunk-rematerialized
    so the (C, C, K) pair tensors of a whole sequence are never held."""
    from repro.kernels.wkv import wkv
    return wkv(r, k, v, logw, u, s0, chunk=chunk, interpret=interpret)


def _wkv_mosaic_fwd(r, k, v, logw, u, s0, chunk, interpret):
    return (wkv_mosaic(r, k, v, logw, u, s0, chunk, interpret),
            (r, k, v, logw, u, s0))


def _wkv_mosaic_bwd(chunk, interpret, res, g):
    _, vjp = jax.vjp(lambda *a: _wkv_chunk(*a, chunk, remat=True), *res)
    return vjp(g)


wkv_mosaic.defvjp(_wkv_mosaic_fwd, _wkv_mosaic_bwd)


def rwkv_time_mix(p: Params, x: jax.Array, cfg: ModelConfig, *,
                  state: jax.Array | None = None, chunk: int = 32,
                  shift_prev: jax.Array | None = None):
    """x: (B,T,D) → (out, final_state). state: (B,H,K,V)."""
    B, T, D = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    xs = _token_shift(x, shift_prev)
    mu = p["mu"].astype(jnp.float32)
    xf = x.astype(jnp.float32)
    xsf = xs.astype(jnp.float32)

    def mix(i):
        return (xf + mu[i] * (xsf - xf)).astype(x.dtype)

    r = (mix(0) @ p["wr"]).reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    k = (mix(1) @ p["wk"]).reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    v = (mix(2) @ p["wv"]).reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    g = jax.nn.silu((mix(3) @ p["wg"]).astype(jnp.float32))
    # data-dependent decay (RWKV6): w = exp(−exp(w0 + tanh(x A) B))
    dd = jnp.tanh((mix(4) @ p["w_a"]).astype(jnp.float32)) @ \
        p["w_b"].astype(jnp.float32)
    logw = -jnp.exp(p["w0"][None, None] + dd)          # (B,T,H·hd) ≤ 0
    logw = logw.reshape(B, T, H, hd).transpose(0, 2, 1, 3)

    if state is None:
        state = jnp.zeros((B, H, hd, hd), jnp.float32)
    if jax.default_backend() == "tpu":
        # Pallas kernel: state + pair tile stay in VMEM (kernels/wkv.py)
        out, s_fin = wkv_mosaic(
            r.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), logw, p["u"], state, chunk)
    else:
        out, s_fin = _wkv_chunk(r.astype(jnp.float32),
                                k.astype(jnp.float32),
                                v.astype(jnp.float32), logw, p["u"], state,
                                chunk)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, H * hd)
    out = rmsnorm(out, p["ln_x"]).astype(jnp.float32) * g
    return (out.astype(x.dtype) @ p["wo"]), s_fin


def rwkv_channel_mix(p: Params, x: jax.Array,
                     shift_prev: jax.Array | None = None) -> jax.Array:
    xs = _token_shift(x, shift_prev)
    mu = p["cm_mu"].astype(jnp.float32)
    xf, xsf = x.astype(jnp.float32), xs.astype(jnp.float32)
    xk = (xf + mu[0] * (xsf - xf)).astype(x.dtype)
    xr = (xf + mu[1] * (xsf - xf)).astype(x.dtype)
    kk = jnp.square(jax.nn.relu(xk @ p["cm_k"]))
    return jax.nn.sigmoid((xr @ p["cm_r"]).astype(jnp.float32)
                          ).astype(x.dtype) * (kk @ p["cm_v"])


# ---------------------------------------------------------------------------
# Mamba-style selective SSM (Hymba branch)
# ---------------------------------------------------------------------------
def init_mamba(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d = cfg.d_model
    di = cfg.ssm_expand * d
    n = cfg.ssm_state
    ks = jax.random.split(key, 6)
    return {
        "in_x": dense_init(ks[0], (d, di), dtype=dtype),
        "in_z": dense_init(ks[1], (d, di), dtype=dtype),
        "w_dt": dense_init(ks[2], (di, 1), scale=0.1, dtype=jnp.float32),
        "dt_bias": jnp.zeros((di,), jnp.float32),
        "w_b": dense_init(ks[3], (di, n), dtype=dtype),
        "w_c": dense_init(ks[4], (di, n), dtype=dtype),
        "log_a": (-jnp.exp(jax.random.normal(ks[5], (di, n), jnp.float32)
                           * 0.5)).astype(jnp.float32),
        "d_skip": jnp.ones((di,), jnp.float32),
        "out": dense_init(jax.random.fold_in(key, 7), (di, d), dtype=dtype),
    }


def mamba_ssm(p: Params, x: jax.Array, cfg: ModelConfig, *,
              state: jax.Array | None = None, chunk: int = 16):
    """x: (B,T,D) → (out, final_state). state: (B, Di, N).

    Sequential scan, chunk-checkpointed: the outer scan carries one state
    per chunk; inner steps recompute in backward (jax.checkpoint)."""
    B, T, D = x.shape
    di, n = p["log_a"].shape
    xb = (x @ p["in_x"]).astype(jnp.float32)            # (B,T,Di)
    z = jax.nn.silu((x @ p["in_z"]).astype(jnp.float32))
    # per-channel step size: broadcast the rank-1 dt over channels + bias
    dt = jax.nn.softplus(xb @ p["w_dt"] + p["dt_bias"][None, None])  # (B,T,Di)
    b_t = xb @ p["w_b"].astype(jnp.float32) / di ** 0.5  # (B,T,N)
    c_t = xb @ p["w_c"].astype(jnp.float32) / di ** 0.5  # (B,T,N)
    u = jax.nn.silu(xb)                                  # (B,T,Di)

    C = min(chunk, T)
    while T % C:          # largest divisor of T not exceeding `chunk`
        C -= 1
    nc = T // C
    if state is None:
        state = jnp.zeros((B, di, n), jnp.float32)

    if jax.default_backend() == "tpu":
        # Pallas kernel: (BD, N) state tile stays in VMEM for the whole
        # sequence (kernels/ssm_scan.py)
        from repro.kernels.ssm_scan import ssm_scan
        ys, s_fin = ssm_scan(u, dt, b_t, c_t, p["log_a"], state, chunk=C)
        y = (ys + u * p["d_skip"][None, None]) * z
        return (y.astype(x.dtype) @ p["out"]), s_fin

    def chunk_body(s, inp):
        xc, dtc, bc, cc = inp   # (B,C,Di), (B,C,Di), (B,C,N), (B,C,N)

        def step(s, i):
            decay = jnp.exp(dtc[:, i][:, :, None] * p["log_a"][None])
            s = decay * s + (dtc[:, i] * xc[:, i])[:, :, None] * \
                bc[:, i][:, None, :]
            y = jnp.einsum("bdn,bn->bd", s, cc[:, i])
            return s, y

        s, ys = lax.scan(step, s, jnp.arange(C))
        return s, ys.transpose(1, 0, 2)                 # (B,C,Di)

    def split(a):
        return a.reshape(B, nc, C, a.shape[-1]).transpose(1, 0, 2, 3)

    s_fin, outs = lax.scan(jax.checkpoint(chunk_body), state,
                           (split(u), split(dt), split(b_t), split(c_t)))
    y = outs.transpose(1, 0, 2, 3).reshape(B, T, di)
    y = (y + u * p["d_skip"][None, None]) * z
    return (y.astype(x.dtype) @ p["out"]), s_fin
