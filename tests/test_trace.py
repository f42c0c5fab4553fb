"""Span tracing, metrics and the cost ledger (DESIGN.md §11).

Four layers:

* `Tracer` invariants — nesting/depth bookkeeping (hypothesis over random
  span trees), ring-buffer bounds, thread-local stacks, the disabled fast
  path; spans on the JAX profiler's clock, and an import without jax;
* Chrome-trace export round-trip — the file json-loads, every complete
  event has non-negative ts/dur in a stable pid, one tid lane per thread,
  and parent/child containment survives the µs conversion;
* metrics semantics — counter monotonicity, gauge set/inc/dec, cumulative
  (Prometheus) histogram buckets, registry get-or-create + kind-mismatch
  rejection, JSON snapshot and text exposition round-trips;
* the cost ledger — `evaluate_plan_terms` reproduces `evaluate_plan`
  exactly, `CostBreakdown.scaled_to` sums to the target within 1e-6,
  `attribute_term_drift` recovers known per-term multipliers, and the
  `PlannerService.observe` path files ledger entries whose shares sum to
  the quoted prediction within 1e-6 (the PR's acceptance criterion).

The traced-vs-untraced numerical equivalence of a `strategy="plan"` sync
step, and the scopes its lowered text carries, run in an 8-host-device
subprocess (the test_sync_pipeline.py pattern). The scopes of the ZeRO-3
step and their cost (none in the optimized program) are checked at smoke
size on one device.
"""
import json
import os
import re
import subprocess
import sys

import pytest

from _hypothesis_stub import given, settings, strategies as st

from repro.runtime.metrics import (Counter, Gauge, Histogram,
                                   MetricsRegistry, default_metrics)
from repro.runtime.trace import (Span, Tracer, default_tracer,
                                 set_default_tracer)


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------
class TestTracer:
    def test_disabled_records_nothing(self):
        tr = Tracer(enabled=False)
        with tr.span("a"):
            with tr.span("b"):
                pass
        tr.instant("c")
        assert tr.spans == []

    def test_nesting_depth_and_order(self):
        tr = Tracer(enabled=True)
        with tr.span("root", k=1):
            with tr.span("child"):
                with tr.span("grandchild"):
                    pass
            with tr.span("sibling"):
                pass
        spans = tr.spans
        # finished in leaf-first order
        assert [s.name for s in spans] == \
            ["grandchild", "child", "sibling", "root"]
        assert [s.depth for s in spans] == [2, 1, 1, 0]
        by_name = {s.name: s for s in spans}
        root, child = by_name["root"], by_name["child"]
        gchild = by_name["grandchild"]
        # containment: children start no earlier and end no later
        assert root.t0 <= child.t0 <= gchild.t0
        assert gchild.t1 <= child.t1 <= root.t1
        assert root.args == {"k": 1} and child.args is None

    def test_instant_is_zero_duration_at_current_depth(self):
        tr = Tracer(enabled=True)
        with tr.span("outer"):
            tr.instant("marker", why="test")
        marker = [s for s in tr.spans if s.name == "marker"][0]
        assert marker.t0 == marker.t1 and marker.depth == 1
        assert marker.duration_s == 0.0
        assert marker.args == {"why": "test"}

    def test_ring_buffer_bounds_memory(self):
        tr = Tracer(capacity=8, enabled=True)
        for i in range(50):
            with tr.span(f"s{i}"):
                pass
        spans = tr.spans
        assert len(spans) == 8
        assert [s.name for s in spans] == [f"s{i}" for i in range(42, 50)]

    def test_exception_inside_span_still_finishes_it(self):
        tr = Tracer(enabled=True)
        with pytest.raises(RuntimeError):
            with tr.span("outer"):
                with tr.span("inner"):
                    raise RuntimeError("boom")
        assert [s.name for s in tr.spans] == ["inner", "outer"]
        assert [s.depth for s in tr.spans] == [1, 0]

    def test_threads_get_independent_stacks_and_tid_lanes(self):
        import threading
        tr = Tracer(enabled=True)

        def work(tag):
            with tr.span(f"outer-{tag}"):
                with tr.span(f"inner-{tag}"):
                    pass

        ts = [threading.Thread(target=work, args=(i,)) for i in range(3)]
        with tr.span("main"):
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        spans = tr.spans
        assert len(spans) == 7
        # per-thread depths are correct even with interleaving
        for s in spans:
            want = 0 if s.name.startswith(("outer", "main")) else 1
            assert s.depth == want, s
        # the main thread's lane is its own (worker idents may be reused
        # by the OS after a join, so workers aren't guaranteed 3 lanes)
        main_tid = {s.tid for s in spans if s.name == "main"}
        worker_tids = {s.tid for s in spans if s.name != "main"}
        assert main_tid and not (main_tid & worker_tids)

    def test_bad_args_rejected(self):
        with pytest.raises(ValueError):
            Tracer(capacity=0)

    def test_default_tracer_swap(self):
        fresh = Tracer(enabled=True)
        old = set_default_tracer(fresh)
        try:
            assert default_tracer() is fresh
        finally:
            set_default_tracer(old)

    @pytest.mark.parametrize("enabled", [False, True])
    def test_span_lands_on_the_profiler_clock(self, tmp_path, enabled):
        """Under a JAX profiler session a span is an annotation in the
        xplane's host plane, ring on or off; the ring keeps it only when
        enabled."""
        import jax
        from jax.profiler import ProfileData
        tr = Tracer(enabled=enabled)
        with jax.profiler.trace(str(tmp_path)):
            with tr.span("test/host_span", step=3):
                jax.numpy.ones(8).block_until_ready()
        assert tr.span("after") is not None        # no session: no error
        xplane = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
        host = [ev.name for plane in ProfileData.from_file(str(xplane)).planes
                if plane.name.startswith("/host")
                for line in plane.lines for ev in line.events]
        assert "test/host_span" in host
        assert [s.name for s in tr.spans] == (
            ["test/host_span"] if enabled else [])


def test_imports_and_spans_without_jax():
    code = (
        "import sys\n"
        "sys.modules['jax'] = sys.modules['jaxlib'] = None\n"
        "from repro.runtime.trace import Tracer\n"
        "t = Tracer(enabled=True)\n"
        "with t.span('a'):\n"
        "    with Tracer().span('b'):\n"
        "        pass\n"
        "assert [s.name for s in t.spans] == ['a']\n"
        "assert not any(m.startswith(('jax.', 'jaxlib.')) "
        "for m in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]


@settings(max_examples=50, deadline=None)
@given(tree=st.recursive(
    st.integers(0, 3),
    lambda kids: st.lists(kids, min_size=1, max_size=3),
    max_leaves=12))
def test_span_tree_invariants(tree):
    """Random nesting structures: every node becomes exactly one span,
    depth equals nesting level, children close before parents, and
    siblings do not overlap."""
    tr = Tracer(enabled=True)
    expected = []

    def walk(node, depth, path):
        name = "/".join(map(str, path)) or "root"
        expected.append((name, depth))
        with tr.span(name):
            if isinstance(node, list):
                for i, kid in enumerate(node):
                    walk(kid, depth + 1, path + [i])

    walk(tree, 0, [])
    spans = tr.spans
    assert len(spans) == len(expected)
    got = {(s.name, s.depth) for s in spans}
    assert got == set(expected)
    by_name = {s.name: s for s in spans}
    for s in spans:
        if s.name == "root":
            continue
        parent = by_name["/" in s.name and s.name.rsplit("/", 1)[0]
                         or "root"]
        # containment: a child's window sits inside its parent's
        assert parent.t0 <= s.t0 and s.t1 <= parent.t1
        assert s.depth == parent.depth + 1
    # monotone: the recorded order is finish order
    t1s = [s.t1 for s in spans]
    assert t1s == sorted(t1s)


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------
class TestChromeExport:
    def _traced(self):
        tr = Tracer(enabled=True)
        with tr.span("step", idx=0):
            with tr.span("rs"):
                pass
            with tr.span("ag"):
                pass
        tr.instant("swap")
        return tr

    def test_round_trip_loads_and_is_well_formed(self, tmp_path):
        tr = self._traced()
        path = tmp_path / "trace.json"
        n = tr.export_chrome(str(path))
        doc = json.loads(path.read_text())
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        events = doc["traceEvents"]
        assert len(events) == n
        xs = [e for e in events if e["ph"] == "X"]
        metas = [e for e in events if e["ph"] == "M"]
        assert len(xs) == 4
        assert {m["name"] for m in metas} == {"process_name",
                                              "thread_name"}
        for e in xs:
            assert e["pid"] == 1 and e["tid"] == 0
            assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        # ts is relative to the earliest span: the root starts at 0
        step = [e for e in xs if e["name"] == "step"][0]
        assert step["ts"] == 0.0
        assert step["args"] == {"idx": 0}

    def test_containment_survives_unit_conversion(self, tmp_path):
        tr = self._traced()
        events = [e for e in tr.to_chrome() if e["ph"] == "X"]
        by = {e["name"]: e for e in events}
        for kid in ("rs", "ag"):
            assert by["step"]["ts"] <= by[kid]["ts"]
            assert by[kid]["ts"] + by[kid]["dur"] <= \
                by["step"]["ts"] + by["step"]["dur"] + 1e-9

    def test_empty_tracer_exports_empty_list(self, tmp_path):
        tr = Tracer(enabled=True)
        path = tmp_path / "empty.json"
        assert tr.export_chrome(str(path)) == 0
        assert json.loads(path.read_text())["traceEvents"] == []

    def test_tid_lanes_stable_per_thread(self):
        import threading
        tr = Tracer(enabled=True)

        def work():
            with tr.span("w1"):
                pass
            with tr.span("w2"):
                pass

        t = threading.Thread(target=work)
        with tr.span("m1"):
            pass
        t.start()
        t.join()
        with tr.span("m2"):
            pass
        xs = [e for e in tr.to_chrome() if e["ph"] == "X"]
        lanes = {}
        for e in xs:
            lanes.setdefault(e["name"][0], set()).add(e["tid"])
        # both main spans share one lane, both worker spans another
        assert len(lanes["m"]) == 1 and len(lanes["w"]) == 1
        assert lanes["m"] != lanes["w"]


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_counter_monotone(self):
        c = Counter("hits_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        g = Gauge("occupancy")
        g.set(0.75)
        g.inc(0.05)
        g.dec(0.30)
        assert g.value == pytest.approx(0.5)

    def test_histogram_cumulative_semantics(self):
        h = Histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count == 5 and h.sum == pytest.approx(56.05)
        cum = h.cumulative()
        assert cum == [(0.1, 1), (1.0, 3), (10.0, 4), (float("inf"), 5)]
        # boundary lands in the bucket whose upper bound it equals
        h2 = Histogram("edge", buckets=(1.0,))
        h2.observe(1.0)
        assert h2.cumulative()[0] == (1.0, 1)

    def test_registry_get_or_create_and_kind_mismatch(self):
        reg = MetricsRegistry()
        c = reg.counter("a_total", "help")
        assert reg.counter("a_total") is c
        with pytest.raises(TypeError):
            reg.gauge("a_total")
        assert reg.histogram("h").bounds == \
            reg.histogram("h").bounds

    def test_snapshot_shape(self):
        reg = MetricsRegistry()
        reg.counter("c_total").inc(2)
        reg.gauge("g").set(1.5)
        reg.histogram("h", buckets=(1.0,)).observe(0.5)
        snap = reg.snapshot()
        assert snap["c_total"] == {"type": "counter", "value": 2.0}
        assert snap["g"] == {"type": "gauge", "value": 1.5}
        assert snap["h"]["count"] == 1
        assert snap["h"]["buckets"][-1] == ["+Inf", 1]
        json.dumps(snap)    # JSON-safe (no raw inf)

    def test_prometheus_text_format(self):
        reg = MetricsRegistry()
        reg.counter("hits_total", "cache hits").inc(3)
        reg.histogram("lat_seconds", buckets=(0.5,)).observe(0.1)
        text = reg.to_prometheus()
        assert "# HELP hits_total cache hits" in text
        assert "# TYPE hits_total counter" in text
        assert "hits_total 3" in text
        assert 'lat_seconds_bucket{le="0.5"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        assert "lat_seconds_sum 0.1" in text
        assert "lat_seconds_count 1" in text

    def test_export_writes_json_and_prom(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("c_total").inc()
        path = tmp_path / "m.json"
        snap = reg.export(str(path))
        assert json.loads(path.read_text()) == snap
        prom = (tmp_path / "m.prom").read_text()
        assert "c_total 1" in prom

    def test_default_registry_shared_by_instrumentation(self):
        from repro.planner.cache import PlanCache
        base = default_metrics().counter("plan_cache_misses_total").value
        PlanCache(capacity=4).get("nope")
        assert default_metrics().counter(
            "plan_cache_misses_total").value == base + 1


# ---------------------------------------------------------------------------
# Cost ledger: per-term decomposition + drift attribution
# ---------------------------------------------------------------------------
class TestCostBreakdown:
    def _plans(self):
        from repro.core.plans import cps, reduce_broadcast, rhd, ring
        return [f(8, 4e6) for f in (ring, rhd, cps, reduce_broadcast)]

    def test_terms_reproduce_evaluate_plan(self):
        from repro.core.cost_model import (PAPER_TABLE5, evaluate_plan,
                                           evaluate_plan_terms)
        p = PAPER_TABLE5["root_sw"]
        for plan in self._plans():
            bd = evaluate_plan_terms(plan, p)
            assert bd.total == pytest.approx(evaluate_plan(plan, p),
                                             rel=1e-12)
            assert all(getattr(bd, t) >= 0.0 for t in bd.TERMS)

    def test_scaled_to_sums_exactly(self):
        from repro.core.cost_model import (PAPER_TABLE5,
                                           evaluate_plan_terms)
        p = PAPER_TABLE5["root_sw"]
        for plan in self._plans():
            for target in (1.0, 3.7e-3, 12.5):
                sc = evaluate_plan_terms(plan, p).scaled_to(target)
                assert sum(sc.as_dict().values()) == \
                    pytest.approx(target, abs=1e-6)

    def test_zero_breakdown_books_alpha(self):
        from repro.core.cost_model import CostBreakdown
        sc = CostBreakdown().scaled_to(2.0)
        assert sc.alpha == 2.0 and sc.total == 2.0
        assert CostBreakdown().shares() == \
            {t: 0.0 for t in CostBreakdown.TERMS}

    def test_shares_are_fractions(self):
        from repro.core.cost_model import PAPER_TABLE5, evaluate_plan_terms
        bd = evaluate_plan_terms(self._plans()[0],
                                 PAPER_TABLE5["root_sw"])
        sh = bd.shares()
        assert sum(sh.values()) == pytest.approx(1.0)
        assert all(0.0 <= v <= 1.0 for v in sh.values())


class TestTermAttribution:
    def test_recovers_known_multipliers(self):
        from repro.core.fitting import attribute_term_drift
        shares = [
            {"alpha": 1.0, "beta": 2.0, "gamma": 0.0, "delta": 0.5,
             "incast": 0.0},
            {"alpha": 2.0, "beta": 1.0, "gamma": 0.0, "delta": 1.0,
             "incast": 0.0},
            {"alpha": 0.5, "beta": 4.0, "gamma": 0.0, "delta": 2.0,
             "incast": 0.0},
        ]
        # cluster truth: β costs 3x the model's price, α and δ on-model
        measured = [s["alpha"] + 3.0 * s["beta"] + s["delta"]
                    for s in shares]
        m = attribute_term_drift(shares, measured)
        assert m["alpha"] == pytest.approx(1.0, abs=1e-8)
        assert m["beta"] == pytest.approx(3.0, abs=1e-8)
        assert m["delta"] == pytest.approx(1.0, abs=1e-8)
        # terms with zero predicted share cannot be attributed
        assert m["gamma"] is None and m["incast"] is None

    def test_empty_and_mismatched_windows(self):
        from repro.core.fitting import TERM_NAMES, attribute_term_drift
        assert attribute_term_drift([], []) == \
            {t: None for t in TERM_NAMES}
        with pytest.raises(ValueError):
            attribute_term_drift([{"alpha": 1.0}], [])


class TestObserveLedger:
    def _service(self):
        from repro.planner.service import PlannerService, RefitPolicy
        return PlannerService(refit_policy=RefitPolicy(enabled=False))

    def test_shares_sum_to_predicted_within_1e6(self):
        svc = self._service()
        for n, size in [(8, 1e6), (8, 4e6), (4, 1e6), (16, 2e6)]:
            out = svc.observe("root_sw", n, size, measured=1e-3)
            e = svc.telemetry.ledger.entries("root_sw")[-1]
            assert sum(e.shares.values()) == \
                pytest.approx(e.predicted, abs=1e-6)
            assert e.predicted == pytest.approx(out["predicted"])
            assert set(e.shares) == {"alpha", "beta", "gamma", "delta",
                                     "incast"}

    def test_ledger_window_grows_and_override_excluded(self):
        from repro.core.cost_model import TPU_V5E
        svc = self._service()
        svc.observe("root_sw", 8, 1e6, 1e-3)
        svc.observe("root_sw", 8, 1e6, 1e-3)
        assert svc.telemetry.ledger.count("root_sw") == 2
        # per-request params overrides are monitoring-only
        svc.observe("root_sw", 8, 1e6, 1e-3, params=TPU_V5E)
        assert svc.telemetry.ledger.count("root_sw") == 2

    def test_refit_event_names_drifting_term(self):
        from repro.core.cost_model import PAPER_TABLE5
        from repro.core.simulator import Simulator
        from repro.core.sync import level_switch_topo
        from repro.planner.service import PlannerService, RefitPolicy
        import dataclasses as dc
        wrong = dict(PAPER_TABLE5)
        wrong["root_sw"] = dc.replace(
            PAPER_TABLE5["root_sw"],
            beta=PAPER_TABLE5["root_sw"].beta / 6)
        svc = PlannerService(params=wrong,
                             refit_policy=RefitPolicy(min_samples=6,
                                                      drift_threshold=0.15,
                                                      cooldown=6))
        sizes = [(8, 1e6), (8, 4e6), (4, 1e6), (8, 1.6e7), (4, 4e6),
                 (8, 2e6), (8, 8e6), (4, 2e6)]
        refit_events = []
        for n, size in sizes * 3:
            resp = svc.get_axis_executable("data", n, size,
                                           level="root_sw")
            topo = level_switch_topo(n, PAPER_TABLE5, "root_sw")
            meas = Simulator(topo, PAPER_TABLE5,
                             unit_bytes=4).simulate(resp.plan).total
            out = svc.observe("root_sw", n, size, meas,
                              predicted=resp.predicted_time,
                              key=resp.key)
            if out["refit"]:
                refit_events = [r for r in svc.refits
                                if r["level"] == "root_sw"]
                break
        assert refit_events, "mis-seeded β never triggered a refit"
        td = refit_events[-1]["term_drift"]
        assert td is not None
        from repro.core.fitting import TERM_NAMES
        assert set(td) == set(TERM_NAMES)
        # β is 6x under-priced. The size-proportional columns (β, γ, δ)
        # are collinear over single-switch plans, so least squares may
        # split the drift among them — but the diagnosis must show the
        # model under-pricing SOMEWHERE well above the stable terms.
        attributed = {k: v for k, v in td.items() if v is not None}
        assert attributed and max(attributed.values()) > 1.5
        # the same event rides the telemetry event log
        ev = [e for e in svc.telemetry.events if e.kind == "refit"][-1]
        assert ev.info["term_drift"] == td

    def test_term_attribution_can_be_disabled(self):
        from repro.planner.service import RefitPolicy
        pol = RefitPolicy(term_attribution=False)
        assert pol.term_attribution is False


# ---------------------------------------------------------------------------
# Scopes: names on the device, free when the step runs
# ---------------------------------------------------------------------------
STEP_SCOPES = ("model/embed", "model/layers", "model/attn", "model/mlp",
               "model/head", "zero3/gather", "zero3/scatter", "optim/clip",
               "optim/adamw")


def _zero3_step_lowered():
    """The manual ZeRO-3 step as the benchmark builds it, at smoke size on
    one device, lowered from shapes."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.sync import SyncConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_manual_train_step
    from repro.models.config import smoke_config
    from repro.models.registry import build
    from repro.optim import AdamWConfig, adamw_init
    api = build(smoke_config(get_config("stablelm-12b")))
    mesh = make_mesh((1, 1), ("data", "model"))
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((1, l.size), l.dtype),
        api.params_spec())
    tokens = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    step = make_manual_train_step(
        api, mesh, AdamWConfig(),
        sync=SyncConfig(strategy="plan", backward_overlap=True))
    return step.lower({"params": params,
                       "opt": jax.eval_shape(adamw_init, params)},
                      {"tokens": tokens, "labels": tokens})


def _stripped(hlo: str) -> str:
    """Optimized HLO text without metadata and stack frames, each name
    renumbered in order of first use."""
    hlo = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                 r".*?\n\n", "", hlo, flags=re.S | re.M)
    hlo = re.sub(r", metadata=\{[^}\n]*\}", "", hlo)
    names: dict[str, str] = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  hlo)


def test_zero3_step_carries_every_scope():
    text = _zero3_step_lowered().as_text(debug_info=True)
    assert [s for s in STEP_SCOPES if s not in text] == []


def test_scopes_leave_the_optimized_program_unchanged(monkeypatch):
    import contextlib

    import jax
    scoped = _zero3_step_lowered().compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _zero3_step_lowered().compile().as_text()
    assert "model/attn" in scoped and "model/attn" not in bare
    assert _stripped(scoped) == _stripped(bare)


# ---------------------------------------------------------------------------
# Traced == untraced: spans must never perturb the numerics
# ---------------------------------------------------------------------------
_DRIVER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import re
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.core.sync import SyncConfig, sync_gradients
from repro.runtime.trace import Tracer, set_default_tracer

AXES = [("x", 8)]
CFG = SyncConfig(strategy="plan", bucket_bytes=4096)


def run_once():
    mesh = make_mesh((8,), ("x",))
    key = jax.random.PRNGKey(7)
    grads = {}
    for i, size in enumerate((1024, 517, 33)):
        key, sub = jax.random.split(key)
        grads[f"l{i}"] = jax.random.normal(sub, (8, size), jnp.float32)
    f = shard_map(
        lambda g: jax.tree.map(
            lambda v: v[None],
            sync_gradients(jax.tree.map(lambda v: v[0], g), AXES, CFG)),
        mesh=mesh, in_specs=P("x"), out_specs=P("x"))
    out = jax.jit(f)(grads)
    text = jax.jit(f).lower(grads).as_text(debug_info=True)
    return {k: np.asarray(v) for k, v in out.items()}, text


set_default_tracer(Tracer(enabled=False))
untraced, _ = run_once()

traced_tracer = Tracer(enabled=True)
set_default_tracer(traced_tracer)
traced, text = run_once()

results = {}
worst = 0.0
for k in untraced:
    diff = np.abs(untraced[k].astype(np.float64)
                  - traced[k].astype(np.float64)).max()
    scale = np.abs(untraced[k]).max() + 1e-30
    worst = max(worst, float(diff / scale))
results["max_rel_diff"] = worst
results["equal_within_1e6"] = bool(worst < 1e-6)
# spans in the ring come from host code only; the step's names are scopes
results["ring_spans_from_traced_code"] = sorted(
    s.name for s in traced_tracer.spans
    if s.name.startswith(("exec/", "bucket/", "sync/", "overlap/")))
results["has_sync_span"] = "sync/bucketed" in text
results["has_round_span"] = re.search(r"exec/round\d+", text) is not None
print("RESULTS " + json.dumps(results))
"""


@pytest.fixture(scope="module")
def diff_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _DRIVER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


def test_traced_sync_equals_untraced(diff_results):
    assert diff_results["equal_within_1e6"], diff_results


def test_traced_sync_recorded_expected_spans(diff_results):
    """The lowered sync carries its scopes; none of its traced code opened
    a host span."""
    assert diff_results["ring_spans_from_traced_code"] == []
    assert diff_results["has_sync_span"]
    assert diff_results["has_round_span"]
