"""Selective-SSM (Mamba-style) chunked scan as a Pallas TPU kernel.

The XLA lowering round-trips the (B, Di, N) state through HBM on every
token (hymba's §Roofline memory term). This kernel keeps a (BD, N) state
tile in VMEM for the whole sequence and unrolls the C steps of each chunk
in-register:

    s_t = exp(dt_t ⊙ log_a) ⊙ s_{t-1} + (dt_t·u_t) ⊗ b_t
    y_t = s_t · c_t                                   (contract N)

Grid: (B, Di/BD, T/C) with the chunk axis innermost (VMEM scratch carries
state across chunks of the same (batch, channel-block) slice). Oracle:
`ref.ssm_scan_ref` (the same recurrence models/recurrence.mamba_ssm runs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(u_ref, dt_ref, b_ref, c_ref, la_ref, s0_ref, y_ref, sT_ref,
                s_scr, *, nc: int, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)        # (C, BD)
    dt = dt_ref[0].astype(jnp.float32)      # (C, BD)
    b = b_ref[0].astype(jnp.float32)        # (C, N)
    c = c_ref[0].astype(jnp.float32)        # (C, N)
    la = la_ref[...].astype(jnp.float32)    # (BD, N)

    s = s_scr[...]                          # (BD, N)
    ys = []
    for t in range(chunk):                  # unrolled; state stays in VREGs
        decay = jnp.exp(dt[t][:, None] * la)            # (BD, N)
        s = decay * s + (dt[t] * u[t])[:, None] * b[t][None, :]
        ys.append(jnp.sum(s * c[t][None, :], axis=-1))  # (BD,)
    y_ref[0] = jnp.stack(ys, axis=0).astype(y_ref.dtype)
    s_scr[...] = s

    @pl.when(ci == nc - 1)
    def _fini():
        sT_ref[0] = s_scr[...].astype(sT_ref.dtype)


def ssm_scan(u: jax.Array, dt: jax.Array, b: jax.Array, c: jax.Array,
             log_a: jax.Array, s0: jax.Array, *, chunk: int = 16,
             block_d: int = 512, interpret: bool = False
             ) -> tuple[jax.Array, jax.Array]:
    """u/dt: (B, T, Di); b/c: (B, T, N); log_a: (Di, N); s0: (B, Di, N).
    Returns (y (B, T, Di), s_final (B, Di, N))."""
    B, T, Di = u.shape
    N = b.shape[-1]
    C = min(chunk, T)
    while T % C:
        C -= 1
    nc = T // C
    bd = min(block_d, Di)
    while Di % bd:
        bd -= 1
    nd = Di // bd

    kernel = functools.partial(_ssm_kernel, nc=nc, chunk=C)
    y, s_fin = pl.pallas_call(
        kernel,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, C, bd), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, C, bd), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, C, N), lambda bi, di, ci: (bi, ci, 0)),
            pl.BlockSpec((1, C, N), lambda bi, di, ci: (bi, ci, 0)),
            pl.BlockSpec((bd, N), lambda bi, di, ci: (di, 0)),
            pl.BlockSpec((1, bd, N), lambda bi, di, ci: (bi, di, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, bd), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, bd, N), lambda bi, di, ci: (bi, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, Di), u.dtype),
            jax.ShapeDtypeStruct((B, Di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret,
        name="ssm_scan",
    )(u, dt, b, c, log_a, s0)
    return y, s_fin
