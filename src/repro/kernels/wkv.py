"""Chunked RWKV6 WKV recurrence as a Pallas TPU kernel.

The XLA lowering of the chunked WKV (models/recurrence._wkv_chunk)
round-trips the (B, H, K, V) state and the (C, C, K) pair tensor through
HBM every chunk — the §Roofline analysis shows this makes the SSM family
memory-bound (rwkv train t_mem 54 s vs t_comp 0.24 s). This kernel keeps
the state AND the pair tile resident in VMEM across the whole sequence:
HBM traffic collapses to the r/k/v/w inputs + the output, one pass.

Layout: grid (B·H, T/C); the chunk axis is innermost so the VMEM scratch
state carries across chunks of the same (b, h) slice (standard Mosaic
accumulator pattern). Math is identical to the oracle `wkv_ref` (the
exponent form exp(Λ_t − Λ_s) keeps every exponent ≤ 0 — unconditionally
stable).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sT_ref,
                s_scr, *, nc: int, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    rc = r_ref[0].astype(jnp.float32)           # (C, K)
    kc = k_ref[0].astype(jnp.float32)
    vc = v_ref[0].astype(jnp.float32)           # (C, V)
    lw = lw_ref[0].astype(jnp.float32)          # (C, K) ≤ 0
    u = u_ref[0].astype(jnp.float32)            # (1, K) broadcast row

    c = rc.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # inclusive Λ as a product with the lower-triangular ones matrix:
    # Mosaic has no cumsum lowering, and the MXU does this in one pass
    linc = _dot((row >= col).astype(jnp.float32), lw)
    lexc = linc - lw                            # exclusive
    s = s_scr[...]                              # (K, V)

    # state contribution: r_t decayed by Λ_{<t}
    o1 = _dot(rc * jnp.exp(lexc), s)            # (C, V)
    # intra-chunk pairs s < t: exponent lexc_t − linc_s ≤ 0. The clamp
    # only touches the masked s ≥ t entries, keeping their exp finite.
    expo = jnp.minimum(lexc[:, None, :] - linc[None, :, :], 0.0)
    pair = rc[:, None, :] * kc[None, :, :] * jnp.exp(expo)   # (C, C, K)
    att = jnp.where(row > col, jnp.sum(pair, axis=-1), 0.0)
    o2 = _dot(att, vc)                          # (C, V)
    # bonus (current token)
    bonus = jnp.sum(rc * kc * u, axis=-1, keepdims=True)
    o3 = bonus * vc
    o_ref[0] = (o1 + o2 + o3).astype(o_ref.dtype)

    # state update: decay by the whole chunk, add k_t (decayed to end) v_t
    ltot = linc[-1:, :]                         # (1, K)
    s_scr[...] = jnp.exp(ltot).T * s + \
        _dot((kc * jnp.exp(ltot - linc)).T, vc)

    @pl.when(ci == nc - 1)
    def _fini():
        sT_ref[0] = s_scr[...].astype(sT_ref.dtype)


def wkv(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
        u: jax.Array, s0: jax.Array, *, chunk: int = 32,
        interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """r/k/logw: (B, H, T, K); v: (B, H, T, V); u: (H, K);
    s0: (B, H, K, V). Returns (out (B, H, T, V), s_final (B, H, K, V))."""
    B, H, T, K = k.shape
    V = v.shape[-1]
    C = min(chunk, T)
    while T % C:
        C -= 1
    nc = T // C
    bh = B * H

    def flat(a):
        return a.reshape((bh,) + a.shape[2:])

    rf, kf, vf, lwf = map(flat, (r, k, v, logw))
    uf = jnp.broadcast_to(u[None, :, None, :], (B, H, 1, K)).reshape(
        bh, 1, K)
    s0f = s0.reshape(bh, K, V)

    kernel = functools.partial(_wkv_kernel, nc=nc, chunk=C)
    out, s_fin = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, C, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, K), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, K, V), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, K, V), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, T, V), r.dtype),
            jax.ShapeDtypeStruct((bh, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
        name="wkv",
    )(rf, kf, vf, lwf, uf, s0f)
    return out.reshape(B, H, T, V), s_fin.reshape(B, H, K, V)
