"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes (as required for every kernel in kernels/)."""
import hashlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_stub import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_reduce import fused_reduce, grouped_reduce
from repro.kernels.rmsnorm import rmsnorm


# ---------------------------------------------------------------------------
# fused_reduce — the paper's δ-optimal N-ary add
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("x,L", [(2, 128), (3, 1000), (8, 4096), (16, 257),
                                 (64, 64), (5, 8192)])
def test_fused_reduce_sweep(x, L, dtype):
    parts = jax.random.normal(jax.random.PRNGKey(x * L), (x, L), jnp.float32)
    parts = parts.astype(dtype)
    got = fused_reduce(parts, interpret=True)
    want = ref.fused_reduce_ref(parts)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("fan_in", [2, 3, 4, 7])
@pytest.mark.parametrize("x,L", [(2, 256), (6, 512), (12, 1000)])
def test_grouped_reduce_sweep(x, L, fan_in):
    parts = jax.random.normal(jax.random.PRNGKey(1), (x, L), jnp.float32)
    got = grouped_reduce(parts, fan_in, interpret=True)
    want = ref.fused_reduce_ref(parts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_grouped_fanin2_matches_chained_oracle():
    parts = jax.random.normal(jax.random.PRNGKey(2), (9, 300), jnp.float32)
    got = grouped_reduce(parts, 2, interpret=True)
    want = ref.chained_reduce_ref(parts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(x=st.integers(2, 10), L=st.integers(1, 600))
def test_fused_reduce_property(x, L):
    parts = jax.random.normal(jax.random.PRNGKey(x + L), (x, L), jnp.float32)
    got = fused_reduce(parts, tile_l=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(parts).sum(0), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention — causal / window / softcap / GQA, shape+dtype sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,T,D", [
    (1, 2, 2, 128, 32), (2, 4, 2, 256, 64), (1, 8, 1, 128, 64),
    (2, 2, 2, 512, 16)])
def test_flash_causal_sweep(B, Hq, Hkv, T, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * T), 3)
    q = jax.random.normal(ks[0], (B, Hq, T, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, T, D), jnp.float32).astype(dtype)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [32, 128, 200])
def test_flash_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 32))
    k = jax.random.normal(ks[1], (1, 2, 256, 32))
    v = jax.random.normal(ks[2], (1, 2, 256, 32))
    got = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("softcap", [20.0, 50.0])
def test_flash_softcap(softcap):
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32)) * 4
    k = jax.random.normal(ks[1], (1, 2, 128, 32)) * 4
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    got = flash_attention(q, k, v, softcap=softcap, interpret=True)
    want = ref.attention_ref(q, k, v, softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_cross_attention_lengths():
    """Tq != Tk (decode-style right-aligned queries)."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32))
    k = jax.random.normal(ks[1], (1, 2, 256, 32))
    v = jax.random.normal(ks[2], (1, 2, 256, 32))
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# splash attention — the trainer's causal global attention on a TPU
# ---------------------------------------------------------------------------
def _attn_cfg(n_kv_heads=4, d_head=64, **kw):
    import dataclasses
    from repro.configs import get_config
    from repro.models.config import smoke_config
    return dataclasses.replace(smoke_config(get_config("stablelm-12b")),
                               n_heads=4, n_kv_heads=n_kv_heads,
                               d_head=d_head, **kw)


def _kernel_in_interpret_mode(monkeypatch):
    """Where the program is lowered for a TPU, `attention` takes the splash
    path: run that path here, with the kernels interpreted."""
    from repro.models import layers
    monkeypatch.setattr(
        layers, "_causal_global",
        lambda q, k, v, scale, dtype, xla: layers.splash_attention(
            q, k, v, scale, interpret=True).astype(dtype))


def _rel(got, want) -> float:
    got, want = (np.asarray(a, np.float32).ravel() for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# bf16 carries 8 significant bits (unit roundoff 2^-9 ~ 2e-3). Against
# the XLA branch, which keeps the probabilities P and the output in f32 up
# to the last cast, the kernel rounds P to bf16 before P.V and rounds q
# once more after the scale: relative errors of a few unit roundoffs
# (up to 6e-3 measured), so the bound is 1.5e-2 on every array.
SPLASH_REL_TOL = 1.5e-2


@pytest.mark.parametrize("T", [512, 1024])
@pytest.mark.parametrize("d_head", [64, 160])
@pytest.mark.parametrize("n_kv_heads", [1, 4], ids=["gqa4to1", "mha"])
def test_splash_path_matches_xla_branch(monkeypatch, n_kv_heads, d_head, T):
    from repro.models.layers import attention, init_attention
    cfg = _attn_cfg(n_kv_heads, d_head)
    p = init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, cfg.d_model),
                          jnp.float32).astype(jnp.bfloat16)
    ct = jax.random.normal(jax.random.PRNGKey(2), (1, T, cfg.d_model),
                           jnp.float32)

    def run():
        def loss(p, x):
            y = attention(p, x, cfg).astype(jnp.float32)
            return (y * ct).sum(), y
        (_, y), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(p, x)
        return y, g

    want_y, (want_gp, want_gx) = run()
    _kernel_in_interpret_mode(monkeypatch)
    got_y, (got_gp, got_gx) = run()
    errs = {"out": _rel(got_y, want_y), "x": _rel(got_gx, want_gx)}
    errs |= {w: _rel(got_gp[w], want_gp[w])
             for w in ("wq", "wk", "wv", "wo")}
    assert max(errs.values()) < SPLASH_REL_TOL, errs


def _canonical_hlo(hlo: str) -> list[list[str]]:
    """Optimized HLO without metadata, each instruction named by what it
    computes (its op, attributes and operands' names, recursively), each
    computation's lines sorted: equal for programs that differ only in the
    order XLA printed independent instructions."""
    hlo = re.sub(r", metadata=\{[^}\n]*\}", "", hlo)
    names: dict[str, str] = {}
    comps, body, header = [], None, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?(%[\w.\-]+) \(.*\) -> .* \{$", line)
        inst = re.match(r"\s+(ROOT )?(%[\w.\-]+) = (.*)$", line)
        if head:
            header, body = head.group(2), []
        elif inst and body is not None:
            root, name, rest = inst.groups()
            line = (root or "") + re.sub(
                r"%[\w.\-]+", lambda m: names.get(m.group(0), "%?"), rest)
            names[name] = "%" + hashlib.sha1(line.encode()).hexdigest()[:16]
            body.append(line)
        elif line == "}" and body is not None:
            body.sort()
            names[header] = "%" + hashlib.sha1(
                "\n".join(body).encode()).hexdigest()[:16]
            comps.append(body)
            body = None
    return sorted(comps)


# (name, config changes, attention keywords, T): the causal global call
# the kernel takes, then each kind of call that keeps its XLA branch
ATTENTION_CALLS = [
    ("causal_global", {}, {}, 512),
    ("non_causal", {}, {"causal": False}, 512),
    ("static_window", {}, {"window": 64}, 512),
    ("traced_window", {}, {"window": "traced"}, 512),
    ("softcap", {"attn_softcap": 30.0}, {}, 512),
    ("kv_override", {}, {"kv_override": "ones"}, 512),
    ("ragged_T", {}, {}, 96),
    ("kv_block", {"attn_kv_block": 128}, {}, 512),
    ("partitioned_over_4_devices", {}, {}, 512),
]


def _attention_call(case):
    from repro.models.layers import attention, init_attention
    _, changes, kw, T = case
    cfg = _attn_cfg(**changes)
    p = init_attention(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((1, T, cfg.d_model), jnp.bfloat16)
    kv = jnp.ones((1, cfg.n_kv_heads, T, cfg.head_dim), jnp.bfloat16)
    kw = {k: {"traced": jnp.int32(0), "ones": (kv, kv)}.get(v, v)
          if isinstance(v, str) else v for k, v in kw.items()}
    if case[0] != "partitioned_over_4_devices":
        return (lambda p, x: attention(p, x, cfg, **kw)), p, x

    def partitioned(p, x):
        """As traced under the auto engine's hook for a 4-device mesh."""
        from repro.models import actsharding
        was = actsharding._HOOK, actsharding._MESH
        actsharding.set_hook(None, types.SimpleNamespace(
            size=4, axis_names=("data", "model")))
        try:
            return attention(p, x, cfg)
        finally:
            actsharding.set_hook(*was)
    return partitioned, p, x


@pytest.mark.parametrize("case", ATTENTION_CALLS, ids=lambda c: c[0])
def test_attention_on_cpu_lowers_the_xla_branch(monkeypatch, case):
    """On the CPU every call lowers to the XLA branch alone, forward and
    gradient: the same program as with the platform dispatch taken out.
    Only causal global self-attention reaches the dispatch at all."""
    from repro.models import layers
    call, p, x = _attention_call(case)

    def programs():
        grad = jax.grad(lambda p, x: jax.checkpoint(call)(p, x).astype(
            jnp.float32).sum(), argnums=(0, 1))
        return [_canonical_hlo(jax.jit(f).lower(p, x).compile().as_text())
                for f in (call, grad)]

    dispatched = []
    as_is = layers._causal_global

    def spy(*a):
        dispatched.append(case[0])
        return as_is(*a)

    monkeypatch.setattr(layers, "_causal_global", spy)
    got = programs()
    assert dispatched == ([case[0]] * 2 if case[0] == "causal_global"
                          else [])
    monkeypatch.setattr(layers, "_causal_global",
                        lambda q, k, v, scale, dtype, xla: xla(q, k, v))
    assert got == programs()


def test_canonical_hlo_sees_a_changed_program():
    f = jax.jit(lambda x: jnp.exp(x) + 1.0)
    g = jax.jit(lambda x: jnp.exp(x) + 2.0)
    x = jnp.ones((8,))
    text = f.lower(x).compile().as_text()
    assert _canonical_hlo(text) == _canonical_hlo(text)
    assert _canonical_hlo(text) != _canonical_hlo(
        g.lower(x).compile().as_text())


def test_attention_path_total_counts_traced_calls():
    from repro.models.layers import attention_path_metric
    from repro.models.registry import build
    from repro.runtime.metrics import MetricsRegistry, set_default_metrics
    reg = MetricsRegistry()
    old = set_default_metrics(reg)
    try:
        for case in ATTENTION_CALLS:
            jax.eval_shape(*_attention_call(case))
        snap = reg.snapshot()
        assert snap[attention_path_metric("kernel")]["value"] == 1
        assert (snap[attention_path_metric("xla")]["value"]
                == len(ATTENTION_CALLS) - 1)
        # a scanned stack of layers is one traced call, whatever its depth
        reg.clear()
        api = build(_attn_cfg(n_layers=3))
        toks = jax.ShapeDtypeStruct((1, 512), jnp.int32)
        jax.eval_shape(lambda p, t: api.loss_fn(p, {"tokens": t,
                                                    "labels": t}),
                       api.params_spec(), toks)
        assert reg.snapshot() == {attention_path_metric("kernel"): {
            "type": "counter", "value": 1.0}}
        prom = reg.to_prometheus()
        assert prom.count("# TYPE attention_path_total counter") == 1
        assert 'attention_path_total{path="kernel"} 1' in prom
    finally:
        set_default_metrics(old)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,d", [(1, 64), (33, 128), (300, 256),
                                    (256, 512)])
def test_rmsnorm_sweep(rows, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(rows), 2)
    x = jax.random.normal(ks[0], (rows, d), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[1], (d,), jnp.float32).astype(dtype)
    got = rmsnorm(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_rmsnorm_3d():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 128))
    w = jnp.ones((128,))
    got = rmsnorm(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ops.py dispatch
# ---------------------------------------------------------------------------
def test_ops_ref_dispatch_cpu():
    from repro.kernels import ops
    parts = jax.random.normal(jax.random.PRNGKey(3), (4, 256))
    np.testing.assert_allclose(np.asarray(ops.fused_reduce(parts)),
                               np.asarray(parts.sum(0)), rtol=1e-5)


# ---------------------------------------------------------------------------
# wkv — chunked RWKV6 recurrence (the SSM-family memory hotspot)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,T,K,chunk", [
    (1, 1, 8, 4, 4), (2, 3, 16, 8, 4), (1, 2, 32, 16, 8),
    (2, 2, 24, 8, 8), (1, 4, 64, 32, 16)])
def test_wkv_kernel_sweep(B, H, T, K, chunk):
    from repro.kernels.wkv import wkv
    ks = jax.random.split(jax.random.PRNGKey(B * T + K), 6)
    r = jax.random.normal(ks[0], (B, H, T, K))
    k = jax.random.normal(ks[1], (B, H, T, K))
    v = jax.random.normal(ks[2], (B, H, T, K))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, K)))
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, K, K)) * 0.1
    got, s_got = wkv(r, k, v, lw, u, s0, chunk=chunk, interpret=True)
    want, s_want = ref.wkv_ref(r, k, v, lw, u, s0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want),
                               rtol=2e-5, atol=2e-5)


def test_wkv_kernel_state_handoff():
    """Running two half-sequences with carried state == one full run."""
    from repro.kernels.wkv import wkv
    B, H, T, K = 1, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    r = jax.random.normal(ks[0], (B, H, T, K))
    k = jax.random.normal(ks[1], (B, H, T, K))
    v = jax.random.normal(ks[2], (B, H, T, K))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, K)))
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    s0 = jnp.zeros((B, H, K, K))
    full, s_full = wkv(r, k, v, lw, u, s0, chunk=8, interpret=True)
    h1, s1 = wkv(r[:, :, :8], k[:, :, :8], v[:, :, :8], lw[:, :, :8],
                 u, s0, chunk=8, interpret=True)
    h2, s2 = wkv(r[:, :, 8:], k[:, :, 8:], v[:, :, 8:], lw[:, :, 8:],
                 u, s1, chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(full[:, :, 8:]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssm_scan — selective-SSM chunked scan (hymba's mamba branch)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,Di,N,chunk,bd", [
    (1, 8, 4, 2, 4, 4), (2, 16, 12, 4, 4, 6), (1, 32, 16, 8, 8, 16),
    (2, 24, 10, 4, 8, 5)])
def test_ssm_scan_kernel_sweep(B, T, Di, N, chunk, bd):
    from repro.kernels.ssm_scan import ssm_scan
    ks = jax.random.split(jax.random.PRNGKey(B * T + Di), 6)
    u = jax.random.normal(ks[0], (B, T, Di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, Di)))
    b = jax.random.normal(ks[2], (B, T, N))
    c = jax.random.normal(ks[3], (B, T, N))
    la = -jnp.exp(jax.random.normal(ks[4], (Di, N)) * 0.5)
    s0 = jax.random.normal(ks[5], (B, Di, N)) * 0.1
    got, sg = ssm_scan(u, dt, b, c, la, s0, chunk=chunk, block_d=bd,
                       interpret=True)
    want, sw = ref.ssm_scan_ref(u, dt, b, c, la, s0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(sg), np.asarray(sw),
                               rtol=2e-5, atol=2e-5)


def test_ssm_scan_state_handoff():
    from repro.kernels.ssm_scan import ssm_scan
    B, T, Di, N = 1, 16, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    u = jax.random.normal(ks[0], (B, T, Di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, Di)))
    b = jax.random.normal(ks[2], (B, T, N))
    c = jax.random.normal(ks[3], (B, T, N))
    la = -jnp.exp(jax.random.normal(ks[4], (Di, N)) * 0.5)
    s0 = jnp.zeros((B, Di, N))
    full, s_full = ssm_scan(u, dt, b, c, la, s0, chunk=4, interpret=True)
    h1, s1 = ssm_scan(u[:, :8], dt[:, :8], b[:, :8], c[:, :8], la, s0,
                      chunk=4, interpret=True)
    h2, s2 = ssm_scan(u[:, 8:], dt[:, 8:], b[:, 8:], c[:, 8:], la, s1,
                      chunk=4, interpret=True)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(full[:, 8:]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=2e-5, atol=2e-5)


def test_ssm_scan_matches_model_recurrence():
    """The kernel recurrence must equal models/recurrence.mamba_ssm's
    inner scan (same inputs derived from a real mamba layer)."""
    import dataclasses
    from repro.configs import get_config
    from repro.models.config import smoke_config
    from repro.models.recurrence import init_mamba, mamba_ssm
    from repro.kernels.ssm_scan import ssm_scan
    cfg = smoke_config(get_config("hymba_1_5b"))
    p = init_mamba(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    y_model, s_model = mamba_ssm(p, x, cfg, chunk=8)
    # recompute the scan inputs exactly as mamba_ssm does
    di, n = p["log_a"].shape
    xb = (x @ p["in_x"]).astype(jnp.float32)
    z = jax.nn.silu((x @ p["in_z"]).astype(jnp.float32))
    dt = jax.nn.softplus(xb @ p["w_dt"] + p["dt_bias"][None, None])
    b_t = xb @ p["w_b"].astype(jnp.float32) / di ** 0.5
    c_t = xb @ p["w_c"].astype(jnp.float32) / di ** 0.5
    u = jax.nn.silu(xb)
    s0 = jnp.zeros((2, di, n), jnp.float32)
    ys, s_fin = ssm_scan(u, dt, b_t, c_t, p["log_a"], s0, chunk=8,
                         block_d=di, interpret=True)
    y = (ys + u * p["d_skip"][None, None]) * z
    y = y.astype(x.dtype) @ p["out"]
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_model),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_fin), np.asarray(s_model),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("T,chunk", [
    (21, 8),      # odd length: chunk falls back to 7, three chunks
    (13, 16),     # one chunk shorter than the requested chunk
    (32, 32),     # exactly one chunk
    (64, 32),     # a chunk boundary with state carried across it
    (5, 1)])      # one-token chunks: the prefix sum is the token itself
def test_wkv_prefix_sum_lengths_and_chunk_boundaries(T, chunk):
    """The in-chunk Λ prefix sum (a product with the lower-triangular ones
    matrix) vs the oracle's cumsum."""
    from repro.kernels.wkv import wkv
    B, H, K = 1, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(T * 31 + chunk), 6)
    r = jax.random.normal(ks[0], (B, H, T, K))
    k = jax.random.normal(ks[1], (B, H, T, K))
    v = jax.random.normal(ks[2], (B, H, T, K))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, K)))
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, K, K)) * 0.1
    got, s_got = wkv(r, k, v, lw, u, s0, chunk=chunk, interpret=True)
    want, s_want = ref.wkv_ref(r, k, v, lw, u, s0, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want),
                               rtol=2e-5, atol=2e-5)


def test_wkv_prefix_sum_zero_rows():
    """Rows of zero decay (log w = 0: Λ stays flat) and zero keys."""
    from repro.kernels.wkv import wkv
    B, H, T, K = 1, 2, 24, 8
    ks = jax.random.split(jax.random.PRNGKey(77), 5)
    r = jax.random.normal(ks[0], (B, H, T, K))
    k = jax.random.normal(ks[1], (B, H, T, K)).at[:, :, 5:9].set(0.0)
    v = jax.random.normal(ks[2], (B, H, T, K))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, K)))
    lw = lw.at[:, :, 8:16].set(0.0).at[:, 1].set(0.0)
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    s0 = jnp.zeros((B, H, K, K))
    got, s_got = wkv(r, k, v, lw, u, s0, chunk=8, interpret=True)
    want, s_want = ref.wkv_ref(r, k, v, lw, u, s0, chunk=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want),
                               rtol=2e-5, atol=2e-5)


def test_wkv_mosaic_gradients_match_chunked_recurrence():
    """The trainable kernel wrapper: Pallas forward, gradient from the
    chunked recurrence — equal to differentiating the recurrence."""
    from repro.models.recurrence import _wkv_chunk, wkv_mosaic
    B, H, T, K = 1, 2, 24, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    r, k, v = (jax.random.normal(x, (B, H, T, K)) for x in ks[:3])
    lw = -jnp.exp(jax.random.normal(ks[3], (B, H, T, K)))
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, K, K)) * 0.1

    def loss(f):
        def run(*a):
            out, s = f(*a)
            return jnp.sum(jnp.sin(out)) + jnp.sum(s * s)
        return jax.grad(run, argnums=tuple(range(6)))

    got = loss(lambda *a: wkv_mosaic(*a, 8, True))(r, k, v, lw, u, s0)
    want = loss(lambda *a: _wkv_chunk(*a, 8))(r, k, v, lw, u, s0)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)
