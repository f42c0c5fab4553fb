"""Driver of a training cell: the manual-engine ZeRO-3 step of
`launch/train.py: make_manual_train_step`, driven as `run_training`
drives it.

Set-up builds the state as `run_training` does (weights made on the
device from the seed by the configuration's reference module, then
`shard_params_zero3` and `adamw_init` on the shards), builds the one step
of the cell's shape, and drives it from the seed through its first
`check_steps` steps. Those steps go through the window's own call and
feed (`one_step`): the batch from the program's `SyntheticLM.batch_at`,
the step, the loss read. They compile the step and give the program's
readings for the comparison. The same state then runs on into the
window, which lasts `seconds`: steps begun are `attempted`; a step
`failed` when its loss is not finite or a fallback counter or guard stat
moved during it. With `trace`, `trace_steps` more steps run under the
profiler after the window. Once the window has closed and the device
memory peak is read, the program's state is freed and the reference
takes the same first steps.

The record holds what a metric file needs to read a configuration's own
mechanisms without an edit here: the configuration file as run
(`config`), the workload's `traffic`, the program's `ModelConfig`
(`model_config`, logged at set-up too, inherited fields with the rest),
the window's change of each program counter and the last value of each
gauge (`counters`, `runtime/metrics.py` names), and every scalar of the
last window step's outputs but its loss (`step_outputs`).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import time

import numpy as np

from harness import (BenchError, CompileClock, TRACE_DIR, device_info,
                     judge, load_module, log, peaks, seed_key)
import counts
import oracle
import traffic

FALLBACK_COUNTERS = (
    "guarded_fallbacks_total", "guarded_wire_fallbacks_total",
    "guarded_demoted_launches_total", "guarded_wire_demoted_launches_total",
    "guarded_timeouts_total", "sync_fallbacks_total")
FALLBACK_STATS = ("fallbacks", "wire_fallbacks", "demoted_launches",
                  "wire_demoted_launches", "timeouts")


def fallbacks() -> float:
    """Every fallback counter and guard stat, summed: a step during which
    this moved did not run the planned path."""
    from repro.core.lower import guard_stats
    from repro.runtime.metrics import default_metrics
    snap = default_metrics().snapshot()
    total = sum(snap.get(n, {}).get("value", 0.0) for n in FALLBACK_COUNTERS)
    for _, st in guard_stats():
        total += sum(st.get(k, 0) for k in FALLBACK_STATS)
    return total


def window_counters(before: dict, after: dict) -> dict:
    """Each counter's change from `before` to `after` and each gauge's
    value at `after`, both `default_metrics().snapshot()`s."""
    out = {}
    for name, m in after.items():
        if m["type"] == "counter":
            out[name] = m["value"] - before.get(name, {}).get("value", 0.0)
        elif m["type"] == "gauge":
            out[name] = m["value"]
    return out


def model_config(config: dict):
    """The program's ModelConfig for the configuration file: its
    registered architecture, with every size the file's `program` map
    names taken from the file; a key `published.<key>` names that key of
    the file's `published` sizes (such as all the experts a router
    chooses from, where the chip holds a share of them)."""
    from repro.configs import get_config

    def size(key):
        if key.startswith("published."):
            return config["published"][key.removeprefix("published.")]
        return config[key]
    fields = {f: size(k) for f, k in config["program"].items()}
    return dataclasses.replace(get_config(config["arch"]), **fields)


def check_layout(api, ref, config: dict) -> None:
    """The reference's weights have the program's tree, shapes and type."""
    import jax
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                        api.params_spec())
    got = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)),
                       jax.eval_shape(lambda: ref.init_params(
                           jax.random.PRNGKey(0), config)))
    if want != got:
        raise BenchError(f"reference weights differ from the program's: "
                         f"{got} vs {want}")


class Faults:
    """Breaks the timed path underneath, for the harness's own tests."""
    FROZEN = "frozen"            # a step returns its state unchanged
    HALF_BATCH = "half_batch"    # half of the batch left out of the mean


def _break(step_fn, fault):
    import jax
    import jax.numpy as jnp
    if fault == Faults.FROZEN:
        def frozen(state, batch):
            _, metrics = step_fn(jax.tree.map(jnp.copy, state), batch)
            return state, metrics
        return frozen
    return step_fn


class Trainer:
    """The program's step for one cell, built once; `start(seed)` makes a
    fresh state from a seed, so several seeds can share one compile."""

    def __init__(self, cell: dict, devices, fault: str | None = None):
        import jax
        from repro.core.sync import SyncConfig
        from repro.launch.mesh import make_mesh
        from repro.launch.train import make_manual_train_step
        from repro.models.registry import build
        from repro.optim import AdamWConfig

        self.wl, self.config = cell["workload"], cell["config"]
        self.tr = self.wl["traffic"]
        self.opt = self.wl["optimizer"]
        self.n = cell["entry"]["chips"]
        self.devs = devices
        self.fault = fault
        self.ref = load_module(cell["reference"])
        self.cfg = model_config(self.config)
        api = build(self.cfg)
        check_layout(api, self.ref, self.config)
        self.mesh = make_mesh((self.n, 1), ("data", "model"), devices=devices)
        self.step_fn = _break(make_manual_train_step(
            api, self.mesh, AdamWConfig(**self.opt),
            sync=SyncConfig(strategy=self.wl["sync"], backward_overlap=True)),
            fault)
        self.B = self.n * self.tr["sequences_per_chip"]
        self.T = self.tr["seq_len"]
        self.init = jax.jit(lambda k: self.ref.init_params(k, self.config))
        self.state = None

    def start(self, seed: int) -> None:
        """Weights on the device in one call, as served (bfloat16), placed
        and given AdamW state as `run_training` does."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.data import DataConfig, SyntheticLM
        from repro.launch.train import shard_params_zero3
        from repro.optim import adamw_init

        self.seed, self.key = seed, seed_key(seed)
        params = shard_params_zero3(self.init(self.key), self.mesh)
        shard = jax.tree.map(lambda x: x.sharding, params)
        opt_state = jax.jit(adamw_init, out_shardings={
            "m": shard, "v": shard,
            "step": NamedSharding(self.mesh, P())})(params)
        self.state = {"params": params, "opt": opt_state}
        tr = self.tr
        self.data = SyntheticLM(DataConfig(
            vocab=self.cfg.vocab, seq_len=self.T, global_batch=self.B,
            seed=seed, zipf_a=tr["zipf_a"], markov_mix=tr["markov_mix"]))
        self.stream = traffic.Stream(self.cfg.vocab, self.T, self.B, seed,
                                     tr["zipf_a"], tr["markov_mix"])
        self.step = 0
        self.input_s, self.step_s, self.phases = [], [], []
        self.fed = {}

    def one_step(self) -> float:
        """The window's call and feed: the batch, the step, the loss read."""
        import jax
        import jax.numpy as jnp
        t0, c0 = time.perf_counter(), time.process_time()
        with jax.profiler.TraceAnnotation("bench/input"):
            host = self.data.batch_at(self.step)
            if self.fault == Faults.HALF_BATCH:
                host["mask"] = oracle.half_mask(host["labels"])
            batch = jax.block_until_ready(jax.tree.map(jnp.asarray, host))
        t1 = time.perf_counter()
        if self.step < self.wl["check_steps"]:
            self.fed[self.step] = host
        with jax.profiler.TraceAnnotation("bench/step"):
            self.state, metrics = self.step_fn(self.state, batch)
        t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/loss_read"):
            loss = float(metrics["loss"])
        t3 = time.perf_counter()
        self.outputs = metrics
        self.input_s.append(t1 - t0)
        self.step_s.append(t3 - t0)
        self.phases.append((self.step, t1 - t0, t2 - t1, t3 - t2,
                            time.process_time() - c0))
        self.step += 1
        return loss

    def counted_step(self) -> tuple[float, bool]:
        """One step: its loss, and whether it failed: a loss that is not
        finite, or a fallback counter or guard stat that moved."""
        before = fallbacks()
        loss = self.one_step()
        return loss, (not math.isfinite(loss)) or fallbacks() != before

    def first_steps(self) -> tuple[dict, int]:
        """The program's readings over the first `check_steps` steps: each
        loss, the first gradient's norms per leaf (from AdamW's m, which
        is (1 - b1) g after one step from m = 0), and the norms per leaf
        of the change the steps made."""
        import jax
        import jax.numpy as jnp
        got = {"losses": []}
        failed = 0
        for s in range(self.wl["check_steps"]):
            loss, bad = self.counted_step()
            got["losses"].append(loss)
            failed += bad
            if s == 0:
                scale = 1.0 / (1.0 - self.opt["b1"])
                got["grad_norms"] = np.asarray(jax.jit(lambda t: jnp.stack([
                    jnp.sqrt(jnp.sum(jnp.square(x))) * scale
                    for x in jax.tree.leaves(t)]))(self.state["opt"]["m"]),
                    np.float64)
        n = self.n

        def layout(x):                     # shard_params_zero3's rows
            flat = x.reshape(-1)
            return jnp.pad(flat, (0, (-flat.size) % n)).reshape(n, -1)

        def change(p, k):
            p0 = jax.tree.map(layout, self.ref.init_params(k, self.config))
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                            - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0))])
        got["change_norms"] = np.asarray(
            jax.jit(change)(self.state["params"], self.key), np.float64)
        return got, failed

    def input_mismatch(self) -> float:
        """Tokens of the first steps' batches that differ from the
        benchmark's own copy of the stream."""
        return float(sum(
            int(np.sum(self.fed[s][k] != v))
            for s in range(self.wl["check_steps"])
            for k, v in self.stream.batch_at(s).items()))

    def step_outputs(self) -> dict:
        """Every scalar the last step returned but its loss, read once."""
        return {k: float(v) for k, v in self.outputs.items()
                if k != "loss" and np.ndim(v) == 0}

    def free(self) -> None:
        self.state = None
        gc.collect()

    def reference(self, mm=oracle.F32, half_batch: bool = False) -> dict:
        batches = [self.stream.batch_at(s)
                   for s in range(self.wl["check_steps"])]
        return oracle.train_readings(self.ref, self.config, self.key,
                                     batches, self.opt, mm=mm,
                                     half_batch=half_batch,
                                     devices=self.devs)


def host_load() -> dict:
    """The host's load average and CPU ticks (all, stolen) so far."""
    try:
        ticks = [int(x) for x in open("/proc/stat").readline().split()[1:]]
    except OSError:
        ticks = [0] * 8
    return {"load": os.getloadavg()[0], "all": sum(ticks),
            "steal": ticks[7] if len(ticks) > 7 else 0}


def log_steps(step_s, input_s, before, after, phases=()) -> None:
    """Where a window's time went on the host, for a run that reads slow:
    each step over twice the median, by phase (input, dispatch, wait for
    the loss, and the process's CPU seconds in the step)."""
    def q(xs, f):
        return 1e3 * sorted(xs)[min(len(xs) - 1, int(f * len(xs)))] if xs else 0
    ticks = max(after["all"] - before["all"], 1)
    log(f"window steps: median {q(step_s, .5):.1f} ms, p90 {q(step_s, .9):.1f},"
        f" max {q(step_s, 1):.1f}; input median {q(input_s, .5):.1f} ms, p90 "
        f"{q(input_s, .9):.1f}, max {q(input_s, 1):.1f}; host load "
        f"{after['load']:.2f}, stolen {100 * (after['steal'] - before['steal']) / ticks:.2f}% "
        f"of CPU ticks")
    med = q(step_s, .5) / 1e3
    for st, a, b, c, cpu in phases:
        if a + b + c > 2 * med:
            log(f"slow step {st}: input {1e3 * a:.1f} ms, dispatch "
                f"{1e3 * b:.1f} ms, wait {1e3 * c:.1f} ms, cpu {1e3 * cpu:.1f} ms")


def run(cell: dict, seed: int, seconds: float, trace: bool, *,
        t_start: float, devices=None, fault: str | None = None) -> dict:
    import jax
    import harness
    from repro.runtime.metrics import default_metrics

    devs = devices if devices is not None else harness.chips(
        cell["entry"]["chips"])
    clock = CompileClock()
    tn = Trainer(cell, devs, fault)
    model = dataclasses.asdict(tn.cfg)
    log(f"model config: {model}")
    tn.start(seed)
    got, setup_failed = tn.first_steps()
    compiles_setup, traces_setup = clock.compiles, clock.traces
    log(f"set-up: compile {clock.seconds:.1f} s in {clock.compiles} "
        f"programs; persistent cache {clock.hits} hits, {clock.misses} "
        f"misses; losses {got['losses']}; failed steps {setup_failed}")

    # -- the window --
    counters_before = default_metrics().snapshot()
    t_w = time.perf_counter()
    setup_s = t_w - t_start
    n_input = len(tn.input_s)
    host_before = host_load()
    attempted = failed = 0
    while time.perf_counter() - t_w < seconds:
        attempted += 1
        failed += tn.counted_step()[1]
    window_s = time.perf_counter() - t_w
    counters = window_counters(counters_before, default_metrics().snapshot())
    step_outputs = tn.step_outputs()
    log_steps(tn.step_s[n_input:], tn.input_s[n_input:], host_before,
              host_load(), tn.phases[n_input:])
    log(f"window counters: {counters}; last step's outputs: {step_outputs}")
    in_window = clock.compiles - compiles_setup
    if in_window or clock.traces > traces_setup:
        log(f"warning: inside the window {in_window} programs compiled and "
            f"{clock.traces - traces_setup} traced or lowered")
    record = {
        "steps": attempted,
        "window_s": window_s,
        "tokens_per_s": attempted * tn.B * tn.T / window_s,
        "setup_s": setup_s,
        "input_s": tn.input_s[n_input:],
        "flops_per_token": counts.model_flops_per_token(tn.config, tn.T),
        # one chip's attention core a step, for the kernels' roofline
        "attention_core": counts.attention_core(
            tn.config, tn.tr["sequences_per_chip"], tn.T),
        "chips": tn.n,
        "peaks": peaks(devs[0].device_kind) if devs[0].platform == "tpu"
        else None,
        "config": tn.config,
        "traffic": tn.tr,
        "model_config": model,
        "counters": counters,
        "step_outputs": step_outputs,
    }

    if trace:
        import tracing
        d = TRACE_DIR / cell["name"]
        tracing.clear(d)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the bench's own spans suffice
        with jax.profiler.trace(str(d), profiler_options=opts):
            for _ in range(tn.wl["trace_steps"]):
                tn.one_step()
        record["trace"] = tracing.reduce(d)
        record["trace_steps"] = tn.wl["trace_steps"]

    device = device_info(devs)
    record["memory_peak_bytes"] = device["memory_peak_bytes"]
    tn.free()

    # -- the comparison, once the window has closed --
    want = tn.reference()
    numbers = oracle.gaps(got, want)
    numbers["input_mismatch"] = tn.input_mismatch()
    log(f"reference: losses {want['losses']}; later steps' loss gaps "
        f"{numbers['later_loss_gaps']}; leaves left out of change_gap "
        f"{numbers['leaves_left_out']}")
    correct, checks = judge(numbers, tn.wl["limits"])
    return {"record": record, "attempted": attempted, "failed": int(failed),
            "correct": correct, "checks": checks, "device": device,
            "readings": {"program": got, "reference": want}}
