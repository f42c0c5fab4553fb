"""Compile-only checks for a described TPU v5e (2x2): the TPU compiler is
installed, so the main path's kernels and one planned collective are
compiled at real sizes for a chip that is described, not attached. This
catches what interpret mode cannot — blocks not aligned to the (8, 128)
tiling, ops Mosaic does not lower, too much VMEM — without a chip.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

BUCKET_LANES = 4 << 20        # a bucket-sized row: 4 Mi f32 lanes
# a row whose last grid step is ragged (44 of 128 tiles) and whose last
# tile is partial
RAGGED_LANES = BUCKET_LANES + 44 * 128 + 5
FAN_IN = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes", [BUCKET_LANES, RAGGED_LANES])
@pytest.mark.parametrize("wire", ["int8", "float8_e4m3fn"])
@pytest.mark.parametrize("kernel", ["quantize", "dequantize",
                                    "quant_reduce", "quant_reduce_requant"])
def test_quant_kernels_compile(one_chip, kernel, wire, lanes):
    from repro.kernels import quant

    nt = -(-lanes // quant.QUANT_TILE)
    Lp = nt * quant.QUANT_TILE
    x = jax.ShapeDtypeStruct((FAN_IN, lanes), jnp.float32,
                             sharding=one_chip)
    q = jax.ShapeDtypeStruct((FAN_IN, Lp), jnp.dtype(wire),
                             sharding=one_chip)
    s = jax.ShapeDtypeStruct((FAN_IN, nt), jnp.float32, sharding=one_chip)
    own = jax.ShapeDtypeStruct((Lp,), jnp.float32, sharding=one_chip)
    calls = {
        "quantize": (lambda a: quant.quantize(a, wire), (x,)),
        "dequantize": (lambda a, b: quant.dequantize(a, b), (q, s)),
        "quant_reduce": (lambda a, b, o: quant.quant_reduce(a, b, o),
                         (q, s, own)),
        "quant_reduce_requant": (
            lambda a, b: quant.quant_reduce_requant(a, b, wire), (q, s)),
    }
    fn, args = calls[kernel]
    assert _has_kernel(_compile(fn, *args))


def test_wkv_compiles_at_rwkv6_widths(one_chip):
    from repro.configs import get_config
    from repro.kernels.wkv import wkv

    cfg = get_config("rwkv6-1.6b")
    B, H, T, K = 1, cfg.n_heads, 4096, cfg.head_dim
    assert (H, K) == (32, 64)
    seq = jax.ShapeDtypeStruct((B, H, T, K), jnp.float32, sharding=one_chip)
    u = jax.ShapeDtypeStruct((H, K), jnp.float32, sharding=one_chip)
    s0 = jax.ShapeDtypeStruct((B, H, K, K), jnp.float32, sharding=one_chip)
    compiled = _compile(lambda *a: wkv(*a, chunk=32),
                        seq, seq, seq, seq, u, s0)
    assert _has_kernel(compiled)


def test_fused_reduce_compiles_at_fan_in_4(one_chip):
    from repro.kernels.fused_reduce import fused_reduce

    parts = jax.ShapeDtypeStruct((FAN_IN, BUCKET_LANES), jnp.float32,
                                 sharding=one_chip)
    assert _has_kernel(_compile(fused_reduce, parts))


def test_planned_allreduce_compiles_to_collective_permutes(topo):
    from jax import shard_map

    from repro.launch.mesh import make_mesh
    from repro.planner.service import PlannerService

    n, size = 4, 1 << 20
    mesh = make_mesh((n,), ("x",), devices=topo.devices[:n])
    sched = PlannerService().get_axis_executable("x", n, size).schedule
    x = jax.ShapeDtypeStruct((n, size), jnp.float32,
                             sharding=NamedSharding(mesh, P("x")))
    f = shard_map(lambda v: sched.allreduce(v[0], "x")[None], mesh=mesh,
                  in_specs=P("x"), out_specs=P("x"))
    text = _compile(f, x).as_text()
    assert "collective-permute" in text
    assert "all-reduce" not in text   # the plan, not a flat psum


def test_splash_attention_compiles_at_the_cell_shape(one_chip):
    """The stablelm-12b cell's attention layer (B=1, T=4096, 32 query and
    8 KV heads of 160, bf16), forward and gradient under jax.checkpoint
    and the `model/attn` scope: the splash forward, dq and dkv kernels,
    each named under `model/attn`, in less scratch memory than the XLA
    branch's 0.74 GiB."""
    from repro.configs import get_config
    from repro.models.layers import attention, init_attention
    from repro.runtime.trace import scope

    cfg = get_config("stablelm-12b")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 160)
    p = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_attention(jax.random.PRNGKey(0), cfg)))
    x = jax.ShapeDtypeStruct((1, 4096, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)

    def layer(p, x):
        with scope("model/attn"):
            return attention(p, x, cfg)

    compiled = _compile(jax.grad(
        lambda p, x: jax.checkpoint(layer)(p, x).astype(jnp.float32).sum(),
        argnums=(0, 1)), p, x)
    text = compiled.as_text()
    op_names = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text,
        flags=re.S)
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert sorted(re.search(r"splash_mha_\w+", n).group(0)
                  for n in op_names) == [
        "splash_mha_dkv_no_residuals", "splash_mha_dq_no_residuals",
        "splash_mha_fwd_residuals"]
    assert all("/model/attn/" in n for n in op_names), op_names
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * (1 << 30)
