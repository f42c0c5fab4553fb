"""Quantized wire kernels (DESIGN.md §13): per-block fp8/int8
quantize/dequantize with per-tile f32 scales, and the fused compressed
N-ary reduce — interpret-mode Pallas vs the pure-jnp oracle, round-trip
error against the Precision error budgets, and the shared lane-padding
helper the kernels inherit from fused_reduce."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_stub import given, settings, strategies as st

from repro.core.cost_model import PRECISIONS
from repro.kernels import ref
from repro.kernels.fused_reduce import fused_reduce, pad_lanes
from repro.kernels.quant import (QUANT_TILE, WIRE_QMAX, dequantize,
                                 quant_reduce, quant_reduce_requant,
                                 quantize, wire_dtype)

WIRES = ["float8_e4m3fn", "int8"]
# wire → the Precision whose error_budget governs it
BUDGET = {"float8_e4m3fn": PRECISIONS["fp8"].error_budget,
          "int8": PRECISIONS["int8"].error_budget}


def _rt_relerr(x, wire, tile=QUANT_TILE):
    q, s = quantize(x, wire, tile=tile, interpret=True)
    back = dequantize(q, s, tile=tile, out_len=x.shape[-1], interpret=True)
    x = np.asarray(x)
    denom = max(float(np.max(np.abs(x))), 1e-30)
    return float(np.max(np.abs(np.asarray(back) - x))) / denom


# ---------------------------------------------------------------------------
# round-trip error bounds per wire dtype
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("W,L", [(1, 128), (4, 4096), (8, 1000), (3, 257)])
def test_roundtrip_within_budget(wire, W, L):
    x = jax.random.normal(jax.random.PRNGKey(W * L), (W, L), jnp.float32)
    assert _rt_relerr(x, wire) < BUDGET[wire]


@pytest.mark.parametrize("wire", WIRES)
def test_roundtrip_scale_outliers(wire):
    """Per-tile scales localize outliers: a 1e4 spike in one tile must
    not wreck the quantization of the others."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 512), jnp.float32)
    x = x.at[0, 5].set(1e4)
    q, s = quantize(x, wire, interpret=True)
    back = np.asarray(dequantize(q, s, out_len=512, interpret=True))
    ref_x = np.asarray(x)
    other = np.abs(back[:, 128:] - ref_x[:, 128:])
    scale = np.max(np.abs(ref_x[:, 128:]))
    assert np.max(other) / scale < BUDGET[wire]


@pytest.mark.parametrize("wire", WIRES)
def test_zero_input_exact(wire):
    """amax == 0 tiles carry scale 0 (not NaN/Inf) and decode to 0."""
    x = jnp.zeros((3, 256), jnp.float32)
    q, s = quantize(x, wire, interpret=True)
    assert np.all(np.asarray(s) == 0.0)
    back = dequantize(q, s, out_len=256, interpret=True)
    assert np.all(np.asarray(back) == 0.0)


# ---------------------------------------------------------------------------
# interpret-mode Pallas ≡ pure-jnp oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("W,L,tile", [(2, 256, 128), (5, 1000, 128),
                                      (8, 384, 64), (3, 130, 128)])
def test_quantize_matches_ref(wire, W, L, tile):
    x = jax.random.normal(jax.random.PRNGKey(L), (W, L), jnp.float32)
    q, s = quantize(x, wire, tile=tile, interpret=True)
    qr, sr = ref.quantize_ref(x, wire, tile)
    np.testing.assert_array_equal(np.asarray(q, np.float32),
                                  np.asarray(qr, np.float32))
    # payloads are bit-exact; the scale division may fold to a
    # reciprocal multiply under interpret-mode jit (±1 ulp)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=2e-7)


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("own", [False, True])
def test_quant_reduce_matches_ref(wire, own):
    x = jax.random.normal(jax.random.PRNGKey(3), (6, 700), jnp.float32)
    q, s = quantize(x, wire, interpret=True)
    o = (jax.random.normal(jax.random.PRNGKey(4), (700,), jnp.float32)
         if own else None)
    got = quant_reduce(q, s, o, out_len=700, interpret=True)
    want = ref.quant_reduce_ref(q, s, o, out_len=700)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# compressed fused reduce vs f32 reference, within the wire budget
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("x,L", [(2, 128), (8, 4096), (16, 257), (5, 1000)])
def test_quant_reduce_vs_f32_reference(wire, x, L):
    parts = jax.random.normal(jax.random.PRNGKey(x + L), (x, L),
                              jnp.float32)
    q, s = quantize(parts, wire, interpret=True)
    got = np.asarray(quant_reduce(q, s, out_len=L, interpret=True))
    want = np.asarray(fused_reduce(parts, interpret=True))
    denom = max(float(np.max(np.abs(want))), 1e-30)
    # the reduce accumulates in f32, so per-element error stays at the
    # round-trip level; x quantized operands compound by at most ~x·ulp,
    # still far inside the per-wire budget for these fan-ins
    assert float(np.max(np.abs(got - want))) / denom < BUDGET[wire]


@pytest.mark.parametrize("wire", WIRES)
def test_quant_reduce_requant_roundtrip(wire):
    """Reduce-and-requantize (the RS hop output stays on the wire):
    decode of the requantized sum ≈ the f32 fused sum."""
    parts = jax.random.normal(jax.random.PRNGKey(11), (4, 500),
                              jnp.float32)
    q, s = quantize(parts, wire, interpret=True)
    qo, so = quant_reduce_requant(q, s, wire, interpret=True)
    assert qo.dtype == wire_dtype(wire)
    back = np.asarray(dequantize(qo[None], so[None], out_len=500,
                                 interpret=True))[0]
    want = np.asarray(parts).sum(0)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(back - want))) / denom < 2 * BUDGET[wire]


# ---------------------------------------------------------------------------
# hypothesis sweep over (x, L, tile) including non-aligned L
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(x=st.integers(2, 8), L=st.integers(1, 700),
       tile=st.sampled_from([64, 128]), wire=st.sampled_from(WIRES))
def test_quant_property(x, L, tile, wire):
    parts = jax.random.normal(jax.random.PRNGKey(x * 701 + L), (x, L),
                              jnp.float32)
    q, s = quantize(parts, wire, tile=tile, interpret=True)
    # padded lanes are whole tiles; scales cover the padded width
    assert q.shape[1] % tile == 0
    assert s.shape == (x, q.shape[1] // tile)
    got = np.asarray(quant_reduce(q, s, tile=tile, out_len=L,
                                  interpret=True))
    assert got.shape == (L,)
    want = np.asarray(parts).sum(0)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < BUDGET[wire]
    # and the oracle agrees bit-for-bit
    np.testing.assert_array_equal(
        got, np.asarray(ref.quant_reduce_ref(q, s, None, tile, L)))


# ---------------------------------------------------------------------------
# shared pad helper (the fused_reduce recursive-pad fix rides this PR)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("L,mult", [(1, 128), (127, 128), (128, 128),
                                    (129, 128), (1000, 128), (60, 64)])
def test_pad_lanes(L, mult):
    x = jnp.arange(2 * L, dtype=jnp.float32).reshape(2, L)
    out = pad_lanes(x, mult)
    assert out.shape[-1] % mult == 0 and out.shape[-1] >= L
    np.testing.assert_array_equal(np.asarray(out[:, :L]), np.asarray(x))
    assert np.all(np.asarray(out[:, L:]) == 0.0)


def test_fused_reduce_nonaligned_single_pad():
    """Regression for the recursive pad path: a non-tile-multiple L pads
    once and slices the output — same values as the aligned oracle."""
    parts = jax.random.normal(jax.random.PRNGKey(5), (7, 333), jnp.float32)
    got = fused_reduce(parts, interpret=True)
    assert got.shape == (333,)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(parts).sum(0),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ops.py dispatch + wire validation
# ---------------------------------------------------------------------------
def test_ops_dispatch_ref_cpu():
    from repro.kernels import ops
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 300), jnp.float32)
    q, s = ops.quantize(x, "int8")
    qr, sr = ref.quantize_ref(x, "int8")
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    got = ops.quant_reduce(q, s, out_len=300)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.quant_reduce_ref(
                                      q, s, None, 128, 300)))


def test_unknown_wire_rejected():
    with pytest.raises((KeyError, ValueError)):
        wire_dtype("float16")
    assert set(WIRE_QMAX) == set(WIRES)
    assert QUANT_TILE == PRECISIONS["fp8"].scale_block


# ---------------------------------------------------------------------------
# scale blocks of SCALE_BLOCK tiles (the TPU-tiling-aligned layout): odd
# lengths, rows longer than one block, all-zero rows
# ---------------------------------------------------------------------------
from repro.kernels.quant import SCALE_BLOCK  # noqa: E402

# tile counts one below, at and past the one-block limit, and a
# multi-block row whose last block is ragged; each L ends mid-tile
BLOCK_NTS = [1, SCALE_BLOCK - 1, SCALE_BLOCK, SCALE_BLOCK + 1,
             2 * SCALE_BLOCK + 44]


def _rows(W, L, zero_row, seed):
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (W, L),
                                     jnp.float32)) * 3.0
    if zero_row is not None:
        x[zero_row] = 0.0
    return jnp.asarray(x)


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("nt", BLOCK_NTS)
def test_quantize_scale_blocks_match_ref(wire, nt):
    L = nt * QUANT_TILE - 5
    x = _rows(3, L, zero_row=1, seed=nt)
    q, s = quantize(x, wire, interpret=True)
    qr, sr = ref.quantize_ref(x, wire)
    assert q.shape == (3, nt * QUANT_TILE) and s.shape == (3, nt)
    np.testing.assert_array_equal(np.asarray(q, np.float32),
                                  np.asarray(qr, np.float32))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=2e-7)
    assert not np.asarray(s)[1].any()          # zero row → zero scales
    back = np.asarray(dequantize(q, s, out_len=L, interpret=True))
    np.testing.assert_array_equal(
        back, np.asarray(ref.dequantize_ref(q, s, out_len=L)))
    assert not back[1].any()                   # … and decodes to exact 0


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("nt", BLOCK_NTS)
@pytest.mark.parametrize("own", [False, True])
def test_quant_reduce_scale_blocks_match_ref(wire, nt, own):
    L = nt * QUANT_TILE - 3
    x = _rows(4, L, zero_row=2, seed=100 + nt)
    q, s = ref.quantize_ref(x, wire)
    o = (jax.random.normal(jax.random.PRNGKey(nt), (L,), jnp.float32)
         if own else None)
    got = np.asarray(quant_reduce(q, s, o, out_len=L, interpret=True))
    want = np.asarray(ref.quant_reduce_ref(q, s, o, out_len=L))
    assert got.shape == (L,)
    # the same f32 products summed, possibly in another order (or fused
    # multiply-adds): bounded by a few roundings of the absolute sum
    terms = np.abs(np.asarray(ref.dequantize_ref(q, s, out_len=L))).sum(0)
    if own:
        terms = terms + np.abs(np.asarray(o))
    assert np.all(np.abs(got - want) <= 4 * np.finfo(np.float32).eps
                  * terms)


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("nt", [SCALE_BLOCK - 1, 2 * SCALE_BLOCK + 44])
def test_quant_reduce_requant_scale_blocks(wire, nt):
    L = nt * QUANT_TILE
    x = _rows(3, L, zero_row=0, seed=200 + nt)
    q, s = quantize(x, wire, interpret=True)
    qo, so = quant_reduce_requant(q, s, wire, interpret=True)
    assert qo.shape == (L,) and so.shape == (nt,)
    back = np.asarray(dequantize(qo[None], so[None], interpret=True))[0]
    want = np.asarray(x).sum(0)
    denom = float(np.max(np.abs(want)))
    assert float(np.max(np.abs(back - want))) / denom < 2 * BUDGET[wire]
