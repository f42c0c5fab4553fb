"""The comparison that decides `correct` for a training cell.

The program's first steps are compared with a plain reference that takes
the same three steps from the same weights and the same batches:

  loss_gap    the relative gap of the first step's loss (the later steps'
              losses follow a first AdamW step of lr in every coordinate,
              which the rounding of near-zero gradients' signs makes
              chaotic: they are reported, `later_loss_gaps`, not compared);
  grad_gap    per leaf, |norm of the first gradient as the optimizer got
              it - the reference's| over max(the reference's norm of that
              leaf, the median leaf's); the worst leaf;
  change_gap  the same for the norm of each leaf's change after 3 steps,
              over the leaves whose reference gradient is at least a
              thousandth of the median leaf's (below that a leaf moves
              under AdamW by rounding alone).

The reference runs the configuration's plain model (`<config>.ref.py`)
under a plain AdamW with global-norm clipping, in float32 with every
product at the highest precision (`F32`). Its control, `FP8`, is the same
reference with every product's operands rounded to float8 (e4m3 forward,
e5m2 for the gradients flowing back, one scale per tensor): the step
below the bfloat16 that the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


class F32:
    """Products in float32 at the highest precision."""

    @staticmethod
    def dot(x, w):
        return jnp.matmul(x, w, precision=HI)

    @staticmethod
    def einsum(eq, a, b):
        return jnp.einsum(eq, a, b, precision=HI)


def _round8(x, dtype):
    """x rounded to float8 `dtype` under one scale per tensor."""
    big = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, big / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _fp8_product(f):
    """f(a, b) with a, b rounded to e4m3 going forward, and the cotangent
    rounded to e5m2 going back: float8 training's recipe."""
    @jax.custom_vjp
    def g(a, b):
        return f(_round8(a, jnp.float8_e4m3fn), _round8(b, jnp.float8_e4m3fn))

    def fwd(a, b):
        a8 = _round8(a, jnp.float8_e4m3fn)
        b8 = _round8(b, jnp.float8_e4m3fn)
        return f(a8, b8), (a8, b8)

    def bwd(res, ct):
        a8, b8 = res
        _, vjp = jax.vjp(f, a8, b8)
        return vjp(_round8(ct, jnp.float8_e5m2))

    g.defvjp(fwd, bwd)
    return g


class FP8:
    """The control: products in float8, everything else as in F32."""

    @staticmethod
    def dot(x, w):
        return _fp8_product(lambda a, b: jnp.matmul(a, b, precision=HI))(x, w)

    @staticmethod
    def einsum(eq, a, b):
        return _fp8_product(
            lambda x, y: jnp.einsum(eq, x, y, precision=HI))(a, b)


def half_mask(labels: np.ndarray) -> np.ndarray:
    """Half of the batch left out: the second half of the rows, or of the
    positions where there is one row."""
    mask = np.ones(labels.shape, np.float32)
    B, T = labels.shape
    if B >= 2:
        mask[B // 2:] = 0
    else:
        mask[:, T // 2:] = 0
    return mask


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_sharding(shape, mesh):
    """Each leaf split over the devices along its largest axis that they
    divide (ZeRO-3's placement, by axis), else kept whole on each."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.devices.size
    axes = [i for i in sorted(range(len(shape)), key=lambda i: -shape[i])
            if shape[i] % n == 0]
    spec = [None] * len(shape)
    if axes and n > 1:
        spec[axes[0]] = "d"
    return NamedSharding(mesh, P(*spec))


def steps_of(ref, cfg: dict, opt: dict, mm, mesh):
    """The reference's jitted pieces: weights in float32 placed over
    `mesh`, the loss with its clipped gradients, and one leaf's update."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd, clip = opt["lr"], opt["weight_decay"], opt["grad_clip"]
    shapes = jax.eval_shape(lambda: ref.init_params(jax.random.PRNGKey(0), cfg))
    placed = jax.tree.map(lambda s: leaf_sharding(s.shape, mesh), shapes)
    init32 = jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(jnp.float32), ref.init_params(k, cfg)),
        out_shardings=placed)
    rows = NamedSharding(mesh, P("d"))

    @functools.partial(jax.jit, out_shardings=(None, placed, None))
    def grads(params, tokens, labels, mask):
        loss, g = jax.value_and_grad(ref.loss)(params, tokens, labels, mask,
                                               cfg, mm)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (gn + 1e-9)), g)
        return loss, g, _leaf_norms(g)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, g, m, v, t):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p), m, v

    def batch(b, mask):
        n = mesh.devices.size
        put = (lambda x: jax.device_put(x, rows)) if b["tokens"].shape[0] % n \
            == 0 else jnp.asarray
        return put(b["tokens"]), put(b["labels"]), put(mask)

    return init32, grads, update, batch


def train_readings(ref, cfg: dict, key, batches: list[dict], opt: dict,
                   mm=F32, half_batch: bool = False,
                   offload: bool | None = None, devices=None) -> dict:
    """Losses of len(batches) steps, per-leaf norms of the first clipped
    gradient, and per-leaf norms of the change after the last step, of
    the reference model `ref` under plain AdamW, its state split over
    `devices` (default: the first).

    The update runs leaf by leaf. With `offload` (by default, where
    parameters, gradients and both moments in float32 would not fit in
    60% of the devices) the moments wait in host memory between steps."""
    from jax.sharding import Mesh
    devices = list(devices or jax.devices()[:1])
    mesh = Mesh(np.array(devices), ("d",))
    init32, grads, update, put = steps_of(ref, cfg, opt, mm, mesh)
    leaves, tdef = jax.tree.flatten(init32(key))
    if offload is None:
        stats = devices[0].memory_stats() or {}
        need = 16 * sum(x.size for x in leaves)
        offload = need > 0.6 * len(devices) * stats.get("bytes_limit",
                                                        float("inf"))
    m = [jnp.zeros_like(x) for x in leaves]
    v = [jnp.zeros_like(x) for x in leaves]
    if offload:
        m, v = [np.asarray(x) for x in m], [np.asarray(x) for x in v]
    shards = [x.sharding for x in leaves]
    losses, grad_norms = [], None
    for t, b in enumerate(batches, start=1):
        mask = half_mask(b["labels"]) if half_batch else \
            np.ones(b["labels"].shape, np.float32)
        loss, g, gnorms = grads(jax.tree.unflatten(tdef, leaves), *put(b, mask))
        g = jax.tree.leaves(g)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = np.asarray(gnorms, np.float64)
        last = t == len(batches)
        for i in range(len(leaves)):
            gi, g[i] = g[i], None
            leaves[i], mi, vi = update(
                leaves[i], gi, jax.device_put(m[i], shards[i]),
                jax.device_put(v[i], shards[i]), jnp.float32(t))
            if offload and not last:
                mi, vi = np.asarray(mi), np.asarray(vi)
            m[i], v[i] = (None, None) if last else (mi, vi)
            del gi, mi, vi
    del m, v
    change = jax.jit(lambda p, k: _leaf_norms(
        jax.tree.map(jnp.subtract, p, init32(k))))(
            jax.tree.unflatten(tdef, leaves), key)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": np.asarray(change, np.float64)}


def gaps(got: dict, ref: dict) -> dict:
    """The three numbers compared, of readings `got` against `ref`."""
    by_step = [abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])]
    gr = np.asarray(ref["grad_norms"])
    med = float(np.median(gr))
    grad_gap = float(np.max(np.abs(np.asarray(got["grad_norms"]) - gr)
                            / np.maximum(gr, med)))
    keep = gr >= 1e-3 * med
    cr = np.asarray(ref["change_norms"])[keep]
    cg = np.asarray(got["change_norms"])[keep]
    cmed = float(np.median(cr))
    change_gap = float(np.max(np.abs(cg - cr) / np.maximum(cr, cmed)))
    return {"loss_gap": float(by_step[0]), "grad_gap": grad_gap,
            "change_gap": change_gap,
            "grad_worst_leaf": int(np.argmax(np.abs(
                np.asarray(got["grad_norms"]) - gr) / np.maximum(gr, med))),
            "change_worst_leaf": int(np.flatnonzero(keep)[np.argmax(
                np.abs(cg - cr) / np.maximum(cr, cmed))]),
            "later_loss_gaps": [float(g) for g in by_step[1:]],
            "leaves_left_out": int(np.sum(~keep))}
