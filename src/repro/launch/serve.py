"""Batched serving driver: continuous prefill + decode over a request queue.

Greedy sampling over the reduced-config model on local devices; the
full-scale serve_step (one token, KV cache of seq_len) is exercised by
launch.dryrun's decode cells. Demonstrates the inference side of the
framework: cache init, prefill, step loop, per-request stop handling.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.config import smoke_config
from repro.models.registry import build
from repro.runtime.metrics import default_metrics
from repro.runtime.trace import default_tracer


@dataclasses.dataclass
class ServeConfig:
    arch: str = "stablelm-12b"
    batch: int = 4
    prompt_len: int = 32
    max_new: int = 32
    cache_len: int = 128
    seed: int = 0


def serve(sc: ServeConfig, smoke: bool = True, on_log=print) -> dict:
    cfg = get_config(sc.arch)
    if smoke:
        cfg = smoke_config(cfg)
    api = build(cfg)

    # Pre-warm the shared plan cache with the tensor-parallel decode
    # AllReduce shape (one per layer, batch × d_model activations over the
    # local devices) and lower the GenTree plan to its executable schedule
    # (DESIGN.md §8). With ≥2 local devices the schedule is executed once
    # under shard_map against lax.psum as a deployment self-check; the
    # decode loop itself is single-host (api.decode_step), so on one
    # device the schedule stays advisory. Returned so callers can act on
    # it (a TP deployment hands it to collectives.allreduce).
    from repro.core.lower import LoweringError
    from repro.planner.service import default_service
    n_dev = len(jax.devices())
    tp_exec = None
    if n_dev > 1:
        try:
            tp_exec = default_service().get_axis_executable(
                "model", n_dev, float(sc.batch * cfg.d_model))
        except LoweringError as e:
            # e.g. a warm disk cache written before block annotations:
            # keep serving on the advisory flat labels, as pre-§8 builds
            tp_plans = default_service().get_axis_plans(
                [("model", n_dev)], float(sc.batch * cfg.d_model))
            desc = ", ".join(
                f"{p.axis}:{p.strategy}{list(p.factors) if p.factors else ''}"
                for p in tp_plans)
            on_log(f"planner: plan not lowerable ({e}); advisory decode "
                   f"plan {desc}")
    if tp_exec is not None:
        # guarded execution (DESIGN.md §12): a failing planned schedule
        # falls back to flat lax.psum instead of failing the deployment
        from repro.core.lower import guard_schedule
        sched = guard_schedule(
            tp_exec.schedule,
            telemetry=default_service().telemetry)
        on_log(f"planner: decode AllReduce executes {tp_exec.algo} plan "
               f"({sched.describe()})")
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        mesh = make_mesh((n_dev,), ("model",))
        probe = jax.random.normal(
            jax.random.PRNGKey(2), (n_dev, sc.batch * cfg.d_model))
        # jitted: an un-jitted shard_map re-traces (and re-dispatches
        # eagerly, round by round) on every call — compiling once makes
        # the self-check ~100x faster on host devices AND gives the
        # timing loop below an executable that measures the collective,
        # not the tracer
        f = jax.jit(shard_map(
            lambda v: sched.allreduce(v[0], "model")[None],
            mesh=mesh, in_specs=P("model"), out_specs=P("model")))
        with default_tracer().span("serve/self_check", n=n_dev,
                                   algo=tp_exec.algo):
            got = np.asarray(f(probe))[0]
        want = np.asarray(probe.sum(0))
        err = float(np.abs(got - want).max() /
                    (np.abs(want).max() + 1e-30))
        on_log(f"planner: executed-schedule self-check rel err {err:.2e}")
        assert err < 1e-5, "executed TP schedule disagrees with psum"
        # The self-check already executed the decode plan — time it and
        # feed the measurement into the planner's online loop (DESIGN.md
        # §10): serving deployments accumulate decode-plan samples the
        # same way training accumulates sync probes, and sustained drift
        # refits the level class and hot-swaps the schedule.
        import time
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(f(probe))
            ts.append(time.perf_counter() - t0)
        measured = sorted(ts)[len(ts) // 2]
        try:
            # no predicted= override: tp_exec.predicted_time is priced
            # at the geometric cache-bucket size (up to ~2x the decode
            # payload); observe's default re-prices at the exact
            # executed size so the residual carries no constant
            # bucket-ratio bias
            obs = default_service().observe(
                "root_sw", n_dev, float(sc.batch * cfg.d_model), measured,
                key=tp_exec.key)
            on_log(f"planner: observed decode plan {measured * 1e3:.3f} "
                   f"ms (predicted {obs['predicted'] * 1e3:.3f} ms, "
                   f"drift {obs['drift']:.2f}"
                   + (", refit" if obs["refit"] else "") + ")")
        except Exception as e:   # advisory measurement — never fail serve
            on_log(f"planner: decode observation skipped ({e!r})")
    elif n_dev == 1:
        on_log("planner: single device, no decode collective needed")
    key = jax.random.PRNGKey(sc.seed)
    params = api.init_params(key)

    prompts = jax.random.randint(
        jax.random.fold_in(key, 1), (sc.batch, sc.prompt_len), 0, cfg.vocab)
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        batch = {"embeds": jax.random.normal(
            key, (sc.batch, sc.prompt_len, cfg.d_model), jnp.bfloat16),
            "mrope_positions": jnp.tile(
                jnp.arange(sc.prompt_len, dtype=jnp.int32)[None, None],
                (3, sc.batch, 1))}
    elif cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            key, (sc.batch, 32, cfg.d_model), jnp.bfloat16)

    prefill = jax.jit(lambda p, b: api.prefill(p, b, cache_len=sc.cache_len))
    decode = jax.jit(api.decode_step)

    tracer = default_tracer()
    metrics = default_metrics()
    with tracer.span("serve/prefill", batch=sc.batch,
                     prompt_len=sc.prompt_len):
        logits, cache = prefill(params, batch)
        tok = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
    metrics.counter("serve_prefill_total", "prefill calls").inc()
    out = [np.asarray(tok)]
    decode_ctr = metrics.counter("serve_decode_steps_total",
                                 "decode steps executed")
    for i in range(sc.max_new - 1):
        with tracer.span("serve/decode", token=i + 1):
            step_batch = {"tokens": tok[:, None]}
            if cfg.family == "vlm":
                emb = jnp.take(params["embed"], tok[:, None], axis=0)
                step_batch = {"embeds": emb}
            logits, cache = decode(params, cache, step_batch)
            tok = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        decode_ctr.inc()
        out.append(np.asarray(tok))
    gen = np.stack(out, axis=1)
    on_log(f"served batch={sc.batch} prompt={sc.prompt_len} "
           f"new={sc.max_new}: first row {gen[0][:8].tolist()}...")
    if tp_exec is not None:
        from repro.core.lower import guard_schedule
        tp_sched = guard_schedule(tp_exec.schedule)   # memoized wrapper
    else:
        tp_sched = None
    return {"tokens": gen, "tp_exec": tp_exec, "tp_schedule": tp_sched}


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-12b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    serve(ServeConfig(arch=args.arch, batch=args.batch,
                      max_new=args.max_new))


if __name__ == "__main__":
    main()
