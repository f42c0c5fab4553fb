"""δ-optimal fused N-ary reduction — the paper's memory-access insight as a
Pallas TPU kernel.

Paper §3.1: a chain of pairwise adds over x blocks costs 3(x−1)·S memory
ops (re-reading the accumulator from HBM every step); a single fused x-ary
add costs (x+1)·S — up to 66.7 % less memory traffic. On TPU the same
economics hold for HBM→VMEM movement: this kernel streams all x operand
tiles into VMEM once per output tile and writes the sum once, accumulating
in a VREG-resident f32 register block.

`grouped_reduce` additionally exposes the paper's HCPS compute pattern: the
x operands are folded with a bounded fan-in f per pass (fan-in trade-off of
Theorem 2), which is what a hierarchical plan's per-stage reduction does.

Block layout: operands (x, L) are tiled along L with TILE_L lanes
(128-aligned for the VPU); the x axis is delivered whole per tile so the
reduction is a single VMEM pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_TILE_L = 4096  # lanes per tile; 4096·x·4B ≤ VMEM budget for x ≤ ~256


def pad_lanes(arr: jax.Array, multiple: int, axis: int = -1) -> jax.Array:
    """Zero-pad `arr` along `axis` to a multiple — the ONE pad used by
    every lane-tiled kernel here and in `kernels.quant`, applied exactly
    once before the single `pallas_call` (never by re-entering the caller,
    which would trace a second kernel per non-aligned size)."""
    axis = axis % arr.ndim
    pad = (-arr.shape[axis]) % multiple
    if not pad:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths)


def _fused_reduce_kernel(parts_ref, out_ref):
    # parts_ref: (x, TILE_L) in VMEM; single pass, f32 accumulation.
    acc = parts_ref[...].astype(jnp.float32).sum(axis=0)
    out_ref[...] = acc.astype(out_ref.dtype)


def fused_reduce(parts: jax.Array, *, tile_l: int = DEFAULT_TILE_L,
                 interpret: bool = False) -> jax.Array:
    """Sum x blocks: (x, L) → (L,), one memory pass ((x+1)·L touches)."""
    x, L = parts.shape
    tile = min(tile_l, L)
    parts = pad_lanes(parts, tile)   # once; sliced back after the call
    grid = (parts.shape[1] // tile,)
    out = pl.pallas_call(
        _fused_reduce_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((x, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((parts.shape[1],), parts.dtype),
        interpret=interpret,
        name="fused_reduce",
    )(parts)
    return out[:L] if out.shape[0] != L else out


def _grouped_reduce_kernel(parts_ref, out_ref, *, fan_in: int):
    # Fold with bounded fan-in per pass (HCPS-style): tree of f-ary adds.
    vals = parts_ref[...].astype(jnp.float32)
    while vals.shape[0] > 1:
        x = vals.shape[0]
        pad = (-x) % fan_in
        if pad:
            vals = jnp.concatenate(
                [vals, jnp.zeros((pad,) + vals.shape[1:], vals.dtype)], axis=0)
        vals = vals.reshape(-1, fan_in, vals.shape[-1]).sum(axis=1)
    out_ref[...] = vals[0].astype(out_ref.dtype)


def grouped_reduce(parts: jax.Array, fan_in: int, *,
                   tile_l: int = DEFAULT_TILE_L,
                   interpret: bool = False) -> jax.Array:
    """Sum x blocks with bounded fan-in f per folding pass: (x, L) → (L,).

    fan_in=2 reproduces the Ring/RHD chained-compute pattern; fan_in=x is
    `fused_reduce`. In-VMEM the intermediate writes are free (VREGs), but
    the schedule mirrors the plan's per-stage reduction structure.
    """
    x, L = parts.shape
    tile = min(tile_l, L)
    parts = pad_lanes(parts, tile)
    grid = (parts.shape[1] // tile,)
    out = pl.pallas_call(
        functools.partial(_grouped_reduce_kernel, fan_in=fan_in),
        grid=grid,
        in_specs=[pl.BlockSpec((x, tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((parts.shape[1],), parts.dtype),
        interpret=interpret,
        name="grouped_reduce",
    )(parts)
    return out[:L] if out.shape[0] != L else out
