"""Plain reference of the stablelm-12b block as the program runs it.

Straightforward jax.numpy in float32, no kernels, no cache, no scan over
layers, written from the equations and not from the program's code:

  x_0     = E[tokens]
  x'      = x + Attn(RMSNorm(x; g1))
  x_next  = x' + W_down (silu(W_gate z) * (W_up z)),   z = RMSNorm(x'; g2)
  logits  = RMSNorm(x_L; g_f) W_head
  loss    = mean over tokens of -log softmax(logits)[label]

RMSNorm(x; g) = x / sqrt(mean(x^2) + 1e-6) * (1 + g). Attention is causal,
grouped (query head h reads key/value head h // (H / H_kv)), with rotary
position embedding over the whole head (rotate-half form, theta from the
configuration) and scale head_dim^-1/2. The softmax of each block of 512
queries is taken over all keys at once: exact, blocked only so it fits.

Where this block departs from the published StableLM 2 block is listed in
stablelm-12b.json under `departures`.

`init_params` makes the weights from a key in one call, in the layout and
bfloat16 type the program serves them in; the harness hands the same
weights to the program and to this reference. Every product goes through
`mm`, so the oracle can run this reference in float32 at the highest
precision or, as its control, in float8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 512
EPS = 1e-6


def shapes(cfg: dict) -> dict:
    """{leaf path: shape} of the parameters, stacked over layers."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    f, V = cfg["intermediate_size"], cfg["vocab_size"]
    return {
        ("embed",): (V, d),
        ("layers", "attn", "wq"): (L, d, H * hd),
        ("layers", "attn", "wk"): (L, d, Hkv * hd),
        ("layers", "attn", "wv"): (L, d, Hkv * hd),
        ("layers", "attn", "wo"): (L, H * hd, d),
        ("layers", "ln1"): (L, d),
        ("layers", "ln2"): (L, d),
        ("layers", "mlp", "wg"): (L, d, f),
        ("layers", "mlp", "wi"): (L, d, f),
        ("layers", "mlp", "wo"): (L, f, d),
        ("lm_head",): (d, V),
        ("ln_f",): (d,),
    }


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return out


def init_params(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Normal(0, fan_in^-1/2) matrices, Normal(0, 0.02) embedding, zero
    norm gains; each leaf from its own fold of `key`."""
    flat = {}
    for i, (path, shape) in enumerate(sorted(shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if path[-1].startswith("ln"):
            flat[path] = jnp.zeros(shape, dtype)
            continue
        scale = 0.02 if path == ("embed",) else shape[-2] ** -0.5
        flat[path] = (jax.random.normal(k, shape, jnp.float32)
                      * scale).astype(dtype)
    return _nest(flat)


def rmsnorm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * (1 + g)


def rope(x, theta):
    """x: (B, heads, T, hd), rotate-half form over the whole head."""
    T, hd = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, z, cfg, mm):
    B, T, _ = z.shape
    H, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    rep = H // Hkv
    q = mm.dot(z, p["wq"]).reshape(B, T, H, hd).transpose(0, 2, 1, 3)
    k = mm.dot(z, p["wk"]).reshape(B, T, Hkv, hd).transpose(0, 2, 1, 3)
    v = mm.dot(z, p["wv"]).reshape(B, T, Hkv, hd).transpose(0, 2, 1, 3)
    q = rope(q, cfg["rope_theta"]).reshape(B, Hkv, rep, T, hd)
    k = rope(k, cfg["rope_theta"])
    bq = min(Q_BLOCK, T)

    @jax.checkpoint
    def block(qb, start):
        s = mm.einsum("bgrqd,bgkd->bgrqk", qb, k) * hd ** -0.5
        qpos = start + jnp.arange(qb.shape[3])[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qpos, s, -jnp.inf)
        return mm.einsum("bgrqk,bgkd->bgrqd", jax.nn.softmax(s, -1), v)

    outs = [block(q[:, :, :, i:i + bq], i) for i in range(0, T, bq)]
    o = jnp.concatenate(outs, 3).reshape(B, H, T, hd)
    return mm.dot(o.transpose(0, 2, 1, 3).reshape(B, T, H * hd), p["wo"])


def mlp(p, z, mm):
    return mm.dot(jax.nn.silu(mm.dot(z, p["wg"])) * mm.dot(z, p["wi"]),
                  p["wo"])


def loss(params, tokens, labels, mask, cfg, mm):
    """Mean next-token NLL over the tokens where `mask` is 1."""
    x = params["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])

        @jax.checkpoint
        def layer(x, lp):
            x = x + attention(lp["attn"], rmsnorm(x, lp["ln1"]), cfg, mm)
            return x + mlp(lp["mlp"], rmsnorm(x, lp["ln2"]), mm)

        x = layer(x, lp)
    logits = mm.dot(rmsnorm(x, params["ln_f"]), params["lm_head"])
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    return (nll * mask).sum() / mask.sum()
