"""Fused RMSNorm Pallas kernel: one HBM pass per row block (read x, write y)
instead of XLA's potential two (mean-of-squares reduce + scale). Rows are
tiled (BR, D) in VMEM; f32 math, input-dtype output."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[...] = (x * rms * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
            block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x: (..., D); w: (D,). Flattens leading dims into row blocks."""
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(x2.shape[0] // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2, w)
    if pad:
        out = out[:rows]
    return out.reshape(shape)
