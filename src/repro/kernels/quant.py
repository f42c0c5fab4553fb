"""Wire-format quantization kernels for compressed collectives.

The paper prices compression honestly: quant/dequant are extra γ/δ memory
passes (Eq. 11's C and D terms), while β·S and the incast term shrink with
the wire payload. These kernels are that trade's execution side — per-tile
symmetric quantization to fp8 (e4m3) or int8 with one f32 abs-max scale per
QUANT_TILE lanes, plus a fused *compressed* N-ary reduce that dequantizes
all x operand tiles in VMEM, accumulates in f32, and (optionally)
requantizes the output, all in a single memory pass — the δ-optimal shape
of `kernels.fused_reduce` carried over to the compressed domain.

Layouts mirror `fused_reduce`: payloads are (W, L) with L tiled along the
lane axis; scales are (W, nt) with nt = ceil(L / tile). Each grid step
covers SCALE_BLOCK tiles of every row: a (W, SCALE_BLOCK·tile) payload
block and its (W, SCALE_BLOCK) scale block, both aligned to the TPU's
(8, 128) tiling (rows of at most SCALE_BLOCK tiles are one whole block).
The last block of a longer row may run past its end: every tile carries
its own scale, so the tiles past the end compute garbage that Pallas
drops on write, and no operand is padded or output sliced for it. A scale of 0 marks
an all-zero (or masked) tile — dequantization multiplies by the scale, so
such tiles decode to exactly 0 regardless of payload bits; the schedule
executor uses this to neutralize masked ppermute rows for free.

Interpret-mode fallback keeps CPU CI running the same code path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fused_reduce import pad_lanes

QUANT_TILE = 128  # lanes per f32 scale; matches Precision.scale_block
# scales per grid step: one lane row of the TPU's (8, 128) tiling, so a
# scale block is tiling-aligned (a (W, 1) block is not)
SCALE_BLOCK = 128

# Symmetric full-scale magnitude per wire dtype.
WIRE_QMAX = {
    "float8_e4m3fn": 448.0,   # finfo(float8_e4m3fn).max
    "int8": 127.0,
}


def wire_dtype(wire: str) -> jnp.dtype:
    if wire not in WIRE_QMAX:
        raise ValueError(f"unsupported wire dtype {wire!r}; "
                         f"one of {sorted(WIRE_QMAX)}")
    return jnp.dtype(wire)


def _encode(vals: jax.Array, wire: str) -> tuple[jax.Array, jax.Array]:
    """vals (..., tile) f32 → (q (..., tile) wire, scale (...) f32).

    scale = amax/qmax, stored as 0 for all-zero tiles so dequant is exact 0.
    """
    qmax = WIRE_QMAX[wire]
    amax = jnp.max(jnp.abs(vals), axis=-1)
    scale = amax / qmax
    safe = jnp.where(scale > 0.0, scale, 1.0)
    y = vals / safe[..., None]
    if wire == "int8":
        y = jnp.clip(jnp.round(y), -qmax, qmax)
    else:
        y = jnp.clip(y, -qmax, qmax)
    return y.astype(jnp.dtype(wire)), jnp.where(amax > 0.0, scale, 0.0)


def _blocking(nt: int) -> tuple[int, int]:
    """(tiles per grid step, grid steps) for nt tiles of a row.

    A scale block is a whole lane row of the (8, 128) layout — or, for
    rows of at most SCALE_BLOCK tiles, the whole array. The last step of
    a longer row is ragged (see the module docstring)."""
    tb = min(nt, SCALE_BLOCK)
    return tb, pl.cdiv(nt, tb)


def _dequant_tiles(q: jax.Array, s: jax.Array, tile: int) -> jax.Array:
    """q (R, tb·tile) wire, s (R, tb) → (R, tb, tile) f32. A zero scale
    decodes to exactly 0, whatever the payload bits."""
    t = q.reshape(q.shape[0], -1, tile).astype(jnp.float32)
    s = s[:, :, None]
    return jnp.where(s != 0.0, t * s, 0.0)


def _quantize_kernel(x_ref, q_ref, s_ref, *, wire: str, tile: int):
    x = x_ref[...].astype(jnp.float32)
    q, s = _encode(x.reshape(x.shape[0], -1, tile), wire)
    q_ref[...] = q.reshape(x.shape)
    s_ref[...] = s


def quantize(x: jax.Array, wire: str = "float8_e4m3fn", *,
             tile: int = QUANT_TILE, interpret: bool = False
             ) -> tuple[jax.Array, jax.Array]:
    """(W, L) f32 → (q (W, Lp) wire, scales (W, nt) f32), Lp = tile-padded L.

    One memory pass: each (W, tb·tile) block is read once, its tb abs-max
    scales and encoded payload written once.
    """
    W, L = x.shape
    nt = -(-L // tile)
    tb, steps = _blocking(nt)
    # only a partial last tile is padded: its zeros are part of the tile
    # the scale is taken over
    x = pad_lanes(x.astype(jnp.float32), tile)
    return pl.pallas_call(
        functools.partial(_quantize_kernel, wire=wire, tile=tile),
        grid=(steps,),
        in_specs=[pl.BlockSpec((W, tb * tile), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((W, tb * tile), lambda i: (0, i)),
                   pl.BlockSpec((W, tb), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((W, nt * tile), wire_dtype(wire)),
                   jax.ShapeDtypeStruct((W, nt), jnp.float32)],
        interpret=interpret,
        name="quantize",
    )(x)


def _dequantize_kernel(q_ref, s_ref, out_ref, *, tile: int):
    out_ref[...] = _dequant_tiles(q_ref[...], s_ref[...], tile).reshape(
        out_ref.shape)


def dequantize(q: jax.Array, scales: jax.Array, *,
               tile: int = QUANT_TILE, out_len: int | None = None,
               interpret: bool = False) -> jax.Array:
    """(q (W, Lp) wire, scales (W, nt)) → (W, out_len or Lp) f32."""
    W, Lp = q.shape
    nt = Lp // tile
    tb, steps = _blocking(nt)
    out = pl.pallas_call(
        functools.partial(_dequantize_kernel, tile=tile),
        grid=(steps,),
        in_specs=[pl.BlockSpec((W, tb * tile), lambda i: (0, i)),
                  pl.BlockSpec((W, tb), lambda i: (0, i))],
        out_specs=pl.BlockSpec((W, tb * tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((W, Lp), jnp.float32),
        interpret=interpret,
        name="dequantize",
    )(q, scales)
    return out if out_len is None or out_len == Lp else out[:, :out_len]


def _quant_reduce_kernel(q_ref, s_ref, out_ref, *, tile: int):
    # q (K, tb·tile) wire, s (K, tb) f32: dequant + K-ary add, one VMEM pass
    deq = _dequant_tiles(q_ref[...], s_ref[...], tile)
    out_ref[...] = deq.reshape(deq.shape[0], -1).sum(axis=0)


def _quant_reduce_own_kernel(q_ref, s_ref, own_ref, out_ref, *, tile: int):
    deq = _dequant_tiles(q_ref[...], s_ref[...], tile)
    out_ref[...] = (deq.reshape(deq.shape[0], -1).sum(axis=0)
                    + own_ref[...].astype(jnp.float32))


def quant_reduce(q: jax.Array, scales: jax.Array,
                 own: jax.Array | None = None, *,
                 tile: int = QUANT_TILE, out_len: int | None = None,
                 interpret: bool = False) -> jax.Array:
    """Fused compressed reduce: (K, Lp) wire + (K, nt) scales [+ own (Lp,)
    f32 resident partial] → (out_len or Lp,) f32.

    Dequantizes the K operand tiles in VMEM and accumulates in f32 without
    materializing any decompressed operand in HBM — (K+1)·S memory touches
    at *wire* width for the operands, exactly what the δ ledger charges.
    """
    K, Lp = q.shape
    nt = Lp // tile
    tb, steps = _blocking(nt)
    q_spec = pl.BlockSpec((K, tb * tile), lambda i: (0, i))
    s_spec = pl.BlockSpec((K, tb), lambda i: (0, i))
    row_spec = pl.BlockSpec((tb * tile,), lambda i: (i,))
    args = [q, scales]
    if own is None:
        kernel = _quant_reduce_kernel
    else:
        kernel = _quant_reduce_own_kernel
        # the resident partial may stop at the row's real length; its
        # tile padding adds nothing
        args.append(pad_lanes(own.astype(jnp.float32), tile))
    out = pl.pallas_call(
        functools.partial(kernel, tile=tile),
        grid=(steps,),
        in_specs=[q_spec, s_spec, row_spec][:len(args)],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((Lp,), jnp.float32),
        interpret=interpret,
        name="quant_reduce",
    )(*args)
    n = Lp if out_len is None else out_len
    return out if n == out.shape[0] else out[:n]


def _quant_reduce_requant_kernel(q_ref, s_ref, qout_ref, sout_ref, *,
                                 wire: str, tile: int):
    acc = _dequant_tiles(q_ref[...], s_ref[...], tile).sum(axis=0)
    qo, so = _encode(acc, wire)                  # (tb, tile), (tb,)
    qout_ref[...] = qo
    sout_ref[...] = so[None, :]


def quant_reduce_requant(q: jax.Array, scales: jax.Array,
                         wire: str = "float8_e4m3fn", *,
                         tile: int = QUANT_TILE, interpret: bool = False
                         ) -> tuple[jax.Array, jax.Array]:
    """Compressed reduce that stays on the wire: (K, Lp) + (K, nt) scales →
    (q (Lp,) wire, scales (nt,) f32), dequant→accumulate→requantize in a
    single memory pass (for schedules that chain folds without a full-
    precision resident partial)."""
    K, Lp = q.shape
    nt = Lp // tile
    tb, steps = _blocking(nt)
    qo, so = pl.pallas_call(
        functools.partial(_quant_reduce_requant_kernel, wire=wire,
                          tile=tile),
        grid=(steps,),
        in_specs=[pl.BlockSpec((K, tb * tile), lambda i: (0, i)),
                  pl.BlockSpec((K, tb), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((tb, tile), lambda i: (i, 0)),
                   pl.BlockSpec((1, tb), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((nt, tile), wire_dtype(wire)),
                   jax.ShapeDtypeStruct((1, nt), jnp.float32)],
        interpret=interpret,
        name="quant_reduce_requant",
    )(q, scales)
    return qo.reshape(-1), so.reshape(-1)
