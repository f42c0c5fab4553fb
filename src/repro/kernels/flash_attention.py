"""Flash attention (TPU Pallas) — causal / sliding-window / softcap / GQA.

Target: TPU MXU. Online-softmax with VMEM scratch accumulators; the KV loop
is the innermost grid dimension so each (batch, head, q-block) revisits its
output block across KV blocks (standard Mosaic pattern). Block shapes are
128-aligned for the MXU; fully-masked KV blocks are skipped via pl.when
(the sliding-window case prunes to O(T·W) work — this is what makes the
`long_500k` shapes tractable for local-attention architectures).

Validated on CPU with interpret=True against kernels.ref.attention_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, softcap: float,
                  bq: int, bk: int, tq: int, tk: int, nk: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    off = tk - tq  # right-aligned query positions
    q_lo = qi * bq + off
    q_hi = q_lo + bq - 1
    k_lo = ki * bk
    k_hi = k_lo + bk - 1
    needed = jnp.bool_(True)
    if causal:
        needed &= k_lo <= q_hi
    if window > 0:
        needed &= k_hi > q_lo - window

    @pl.when(needed)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)              # (bq, 1)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _fini():
        o_ref[0, 0] = (acc_scr[...] /
                       (l_scr[...] + 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Tq, D); k/v: (B, Hkv, Tk, D) with Hq % Hkv == 0."""
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    group = Hq // Hkv
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    assert Tq % bq == 0 and Tk % bk == 0, (Tq, bq, Tk, bk)
    nq, nk = Tq // bq, Tk // bk
    s = scale if scale is not None else D ** -0.5

    kernel = functools.partial(
        _flash_kernel, scale=s, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, tq=Tq, tk=Tk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
