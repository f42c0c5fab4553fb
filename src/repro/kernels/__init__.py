"""Pallas TPU kernels for the framework's compute hot-spots.

fused_reduce — the paper's δ-optimal N-ary reduction (core contribution's
compute half); flash_attention — long-context attention; wkv — the RWKV6
chunked recurrence (SSM-family memory bottleneck); rmsnorm — fused
normalization. Each has a pure-jnp oracle in ref.py and a jit'd wrapper in
ops.py; interpret=True validates kernel bodies on CPU.

The trainer's causal attention does not use flash_attention: on a TPU,
`models/layers.py: attention` runs JAX's splash attention kernels
(`jax.experimental.pallas.ops.tpu.splash_attention`), which have a
backward. flash_attention stays forward-only, for its own callers.
"""
from . import ops, ref  # noqa: F401
