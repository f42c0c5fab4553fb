"""What every cell of the benchmark shares: where things are, how they are
found by name, the chip check, the caches, and the result line.

A cell (an entry of `workloads` in BENCHMARK.json) is found by its name:

  bench/workloads/<cell>.json     its traffic and the driver that runs it
  bench/configs/<config>.json     the model configuration as it is run, and
                                  its `tiny` sizes for the CPU tests
  bench/configs/<config>.ref.py   its plain reference (imports no program code)
  bench/drivers/<kind>.py         the driver named by the workload's `kind`
  bench/metrics/<metric>.py       one per-layer metric: `read(run) -> float|None`

Adding a cell or a metric is adding files and entries; no file here names
a cell, a configuration or a metric.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
# fixed paths inside the checkout: the compile cache's key holds its path
JAX_CACHE_DIR = CACHE / "jax"
PLAN_CACHE = CACHE / "plans.json"
TRACE_DIR = CACHE / "trace"

GIB = 1 << 30


class BenchError(Exception):
    """The run cannot produce a result: no chip, an unknown device, a
    missing file. The harness exits non-zero and prints no result."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str | None = None):
    """Import a file of the bench by its path (names hold `-` and `.`)."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    mod_name = name or "bench_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(BENCH)))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, resolved from BENCHMARK.json and files."""
    bm = benchmark(root)
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    bench = root / "bench"
    conf_entry = next(c for c in bm["configs"] if c["name"] == entry["config"])
    workload = load_json(bench / "workloads" / f"{name}.json")
    config = load_json(root / conf_entry["file"])
    e2e = [m for m in bm["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bm["per_layer"]
                 if name in m.get("workloads", [name])]
    return {
        "name": name,
        "entry": entry,
        "workload": workload,
        "config": config,
        "reference": bench / "configs" / f"{entry['config']}.ref.py",
        "driver": bench / "drivers" / f"{workload['kind']}.py",
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def at_test_size(config: dict) -> dict:
    """The configuration at the size the CPU tests run it: the file's
    `tiny` sizes laid over it, those under `tiny.published` over its
    `published` sizes."""
    tiny = dict(config["tiny"])
    published = {**config["published"], **tiny.pop("published", {})}
    return {**config, **tiny, "published": published}


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       f"bench_metric_{name.replace('.', '_')}").read


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------
def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The published peaks of `device_kind`; an unknown device is an error,
    never a default."""
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table[device_kind]


def chips(want: int):
    """The first `want` TPU devices; anything else is an error."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < want:
        raise BenchError(f"{want} chips needed, {len(devs)} found")
    peaks(devs[0].device_kind)
    return devs[:want]


def use_caches() -> None:
    """JAX's persistent compile cache and the planner's disk cache, at
    fixed paths in the checkout. Set before JAX is imported."""
    CACHE.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(JAX_CACHE_DIR)
    os.environ["REPRO_PLAN_CACHE"] = str(PLAN_CACHE)
    # every program goes to the cache, the small ones too, so that a warm
    # run compiles nothing
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


class CompileClock:
    """JAX's own compile-duration events, and persistent-cache hits and
    misses (after chip_smoke.py); traces and lowerings are counted too, as
    a step that is traced again costs the host seconds without any
    backend compile."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.traces = 0
        self.trace_seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.traces += 1
            self.trace_seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def seed_key(seed: int):
    """A PRNG key from a seed of any size: JAX keeps only the low 32 bits
    of an integer seed, so the high bits are folded in."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------
def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number compared beside its limit; correct when every one is
    at or under its limit and none is missing or not finite."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and math.isfinite(v) and v <= limit
        ok &= good
        checks[name] = {"value": v, "limit": limit}
    return ok, checks


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: dict,
                breakdown: dict | None = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks            # last, as the contract asks
    return json.dumps(out)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
