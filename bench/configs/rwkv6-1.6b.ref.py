"""Plain reference of the rwkv6-1.6b (Finch) block as the program runs it.

Straightforward jax.numpy in float32, the WKV recurrence as a plain
token-by-token scan, written from the equations (arXiv:2404.05892, with
the program's departures listed in rwkv6-1.6b.json) and not from the
program's code. Per layer, with RMSNorm(x; g) = x / sqrt(mean(x^2) +
1e-6) * (1 + g) and shift(x)_t = x_{t-1} (zero at t = 0):

  z       = RMSNorm(x; g1),  m_i = z + mu_i (shift(z) - z),  i = 0..4
  r, k, v = m_0 W_r, m_1 W_k, m_2 W_v           (per head of size K)
  g       = silu(m_3 W_g)
  w_t     = exp(-exp(w0 + tanh(m_4 A) B))       (per channel, in (0, 1))
  o_t     = r_t (S_{t-1} + diag(u) k_t^T v_t),  S_t = diag(w_t) S_{t-1} + k_t^T v_t
  x      += (RMSNorm(o; g_x) * g) W_o
  z'      = RMSNorm(x; g2),  k' = z' + c_0 (shift(z') - z'),  r' = z' + c_1 (...)
  x      += sigmoid(r' C_r) * (relu(k' C_k)^2 C_v)

then logits = RMSNorm(x; g_f) W_head and the mean next-token NLL. The
scan runs over chunks of 64 tokens, each rematerialized in the backward
pass so that it fits; the arithmetic is the token-by-token recurrence.

`init_params` makes the weights from a key in one call, in the layout and
types the program serves them in; every product goes through `mm`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6
SCAN_CHUNK = 64


def shapes(cfg: dict) -> dict:
    """{leaf path: (shape, dtype)} of the parameters, stacked over layers."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    K, f, V = cfg["head_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, lora = d // K, cfg["time_decay_extra_dim"]
    bf, f32 = jnp.bfloat16, jnp.float32
    lay = {
        "mu": ((L, 5, d), bf), "wr": ((L, d, d), bf), "wk": ((L, d, d), bf),
        "wv": ((L, d, d), bf), "wg": ((L, d, d), bf), "wo": ((L, d, d), bf),
        "w0": ((L, d), f32), "w_a": ((L, d, lora), bf),
        "w_b": ((L, lora, d), bf), "u": ((L, H, K), f32),
        "ln_x": ((L, d), bf), "cm_mu": ((L, 2, d), bf),
        "cm_k": ((L, d, f), bf), "cm_v": ((L, f, d), bf),
        "cm_r": ((L, d, d), bf), "ln1": ((L, d), bf), "ln2": ((L, d), bf),
    }
    out = {("layers", k): v for k, v in lay.items()}
    out.update({("embed",): ((V, d), bf), ("lm_head",): ((d, V), bf),
                ("ln_f",): ((d,), bf)})
    return out


def init_params(key, cfg: dict) -> dict:
    """Normal(0, fan_in^-1/2) matrices (decay LoRA up: 0.01), Normal(0,
    0.02) embedding, Uniform(0, 1) mixing coefficients, w0 ~ N(-2, 0.5),
    u ~ N(0, 0.1), zero norm gains; each leaf from its own fold of `key`."""
    out: dict = {}
    for i, (path, (shape, dtype)) in enumerate(sorted(shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        name = path[-1]
        if name.startswith("ln"):
            x = jnp.zeros(shape, jnp.float32)
        elif name in ("mu", "cm_mu"):
            x = jax.random.uniform(k, shape, jnp.float32)
        elif name == "w0":
            x = jax.random.normal(k, shape, jnp.float32) * 0.5 - 2.0
        elif name == "u":
            x = jax.random.normal(k, shape, jnp.float32) * 0.1
        else:
            scale = (0.02 if name == "embed" else 0.01 if name == "w_b"
                     else shape[-2] ** -0.5)
            x = jax.random.normal(k, shape, jnp.float32) * scale
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[name] = x.astype(dtype)
    return out


def rmsnorm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * (1 + g)


def shift(x):
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def wkv(r, k, v, w, u):
    """r, k, v, w: (B, T, H, K); u: (H, K). The recurrence token by token."""
    B, T, H, K = r.shape

    def token(S, inp):
        rt, kt, vt, wt = inp                         # (B, H, K)
        kv = kt[..., :, None] * vt[..., None, :]     # (B, H, K, V)
        o = jnp.sum(rt[..., :, None] * (S + u[None, :, :, None] * kv), -2)
        return wt[..., :, None] * S + kv, o

    @jax.checkpoint
    def chunk(S, inp):
        return jax.lax.scan(token, S, inp)

    def split(a):        # (B, T, H, K) -> (T/C, C, B, H, K)
        return a.transpose(1, 0, 2, 3).reshape(T // SCAN_CHUNK, SCAN_CHUNK,
                                                B, H, K)

    S0 = jnp.zeros((B, H, K, K), jnp.float32)
    _, o = jax.lax.scan(chunk, S0, tuple(map(split, (r, k, v, w))))
    return o.reshape(T, B, H, K).transpose(1, 0, 2, 3)


def layer(p, x, cfg, mm):
    B, T, d = x.shape
    K = cfg["head_size"]
    H = d // K
    z = rmsnorm(x, p["ln1"])
    zs = shift(z)
    m = [z + p["mu"][i] * (zs - z) for i in range(5)]
    r, k, v = (mm.dot(m[i], p[w]).reshape(B, T, H, K)
               for i, w in enumerate(("wr", "wk", "wv")))
    g = jax.nn.silu(mm.dot(m[3], p["wg"]))
    w = jnp.exp(-jnp.exp(p["w0"] + mm.dot(jnp.tanh(mm.dot(m[4], p["w_a"])),
                                          p["w_b"])))
    o = wkv(r, k, v, w.reshape(B, T, H, K), p["u"]).reshape(B, T, d)
    x = x + mm.dot(rmsnorm(o, p["ln_x"]) * g, p["wo"])
    z = rmsnorm(x, p["ln2"])
    zs = shift(z)
    kk = z + p["cm_mu"][0] * (zs - z)
    rr = z + p["cm_mu"][1] * (zs - z)
    return x + jax.nn.sigmoid(mm.dot(rr, p["cm_r"])) * mm.dot(
        jnp.square(jax.nn.relu(mm.dot(kk, p["cm_k"]))), p["cm_v"])


def loss(params, tokens, labels, mask, cfg, mm):
    """Mean next-token NLL over the tokens where `mask` is 1."""
    x = params["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = jax.checkpoint(lambda x, lp: layer(lp, x, cfg, mm))(x, lp)
    logits = mm.dot(rmsnorm(x, params["ln_f"]), params["lm_head"])
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return (nll * mask).sum() / mask.sum()
