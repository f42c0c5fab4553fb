"""AdamW with decoupled weight decay and global-norm clipping.

Optimizer state (m, v) is kept in f32 regardless of param dtype; ZeRO
partitioning is a *sharding* concern — the launcher shards these leaves
over the data axis (ZeRO-1) via NamedSharding, the math here is
sharding-agnostic.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.runtime.trace import scope

Params = Any


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


def adamw_init(params: Params) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def clip_by_global_norm(grads: Params, max_norm: float
                        ) -> tuple[Params, jax.Array]:
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (gn + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads), gn


def adamw_update(params: Params, grads: Params, opt_state: dict,
                 cfg: AdamWConfig, lr: jax.Array | float | None = None
                 ) -> tuple[Params, dict, jax.Array]:
    """Returns (new_params, new_state, grad_norm)."""
    with scope("optim/clip"):
        if cfg.grad_clip > 0:
            grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
        else:
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in jax.tree.leaves(grads)))
    with scope("optim/adamw"):
        step = opt_state["step"] + 1
        lr = cfg.lr if lr is None else lr
        b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
        b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            gf = g.astype(jnp.float32)
            m = cfg.b1 * m + (1 - cfg.b1) * gf
            v = cfg.b2 * v + (1 - cfg.b2) * gf * gf
            mh = m / b1c
            vh = v / b2c
            pf = p.astype(jnp.float32)
            pf = pf - lr * (mh / (jnp.sqrt(vh) + cfg.eps)
                            + cfg.weight_decay * pf)
            return pf.astype(p.dtype), m, v

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        flat_m = jax.tree.leaves(opt_state["m"])
        flat_v = jax.tree.leaves(opt_state["v"])
        new_p, new_m, new_v = [], [], []
        for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
            a, b, c = upd(p, g, m, v)
            new_p.append(a)
            new_m.append(b)
            new_v.append(c)
    return (jax.tree.unflatten(tdef, new_p),
            {"m": jax.tree.unflatten(tdef, new_m),
             "v": jax.tree.unflatten(tdef, new_v),
             "step": step},
            gnorm)
