"""Runtime telemetry (repro.runtime.telemetry): rings + streaming stats,
residual tracking, arrival-offset estimation, re-measure windows, and the
shared watchdog/planner datapath (DESIGN.md §10)."""
import pytest

from repro.runtime.telemetry import (ArrivalEstimator, CostLedger,
                                     LedgerEntry, LevelSample,
                                     ResidualTracker, Telemetry, TimingRing)


# ---------------------------------------------------------------------------
# TimingRing
# ---------------------------------------------------------------------------
class TestTimingRing:
    def test_mean_and_count(self):
        r = TimingRing(capacity=8)
        for v in (1.0, 2.0, 3.0):
            r.add(v)
        assert r.count == 3 and r.total == 3
        assert r.mean() == pytest.approx(2.0)
        assert r.last == 3.0

    def test_wraparound_keeps_freshest_window(self):
        r = TimingRing(capacity=4)
        for v in range(10):
            r.add(float(v))
        assert r.count == 4 and r.total == 10
        assert r.window() == [6.0, 7.0, 8.0, 9.0]
        assert r.mean() == pytest.approx(7.5)

    def test_percentiles(self):
        r = TimingRing(capacity=16)
        for v in range(1, 11):           # 1..10
            r.add(float(v))
        assert r.percentile(0) == 1.0
        assert r.percentile(100) == 10.0
        assert r.percentile(50) == pytest.approx(5.5)

    def test_ewma_halflife_decay(self):
        r = TimingRing(capacity=8, halflife=1)
        r.add(0.0)                        # seeds the EWMA
        r.add(2.0)                        # k = 2^-1 = 0.5
        assert r.ewma == pytest.approx(1.0)

    def test_baseline_false_excluded_from_ewma_kept_in_window(self):
        r = TimingRing(capacity=8)
        r.add(1.0)
        r.add(100.0, baseline=False)      # straggler
        assert r.ewma == pytest.approx(1.0)
        assert r.count == 2 and 100.0 in r.window()

    def test_reset(self):
        r = TimingRing(capacity=4)
        r.add(1.0)
        r.reset()
        assert r.count == 0 and r.ewma is None and r.mean() == 0.0

    def test_bad_capacity(self):
        with pytest.raises(ValueError):
            TimingRing(capacity=0)


# ---------------------------------------------------------------------------
# ResidualTracker
# ---------------------------------------------------------------------------
class TestResidualTracker:
    def test_relative_residuals(self):
        t = ResidualTracker()
        rel = t.record(predicted=1.0, measured=1.5)
        assert rel == pytest.approx(0.5)
        assert t.record(1.0, 0.5) == pytest.approx(-0.5)

    def test_drift_is_median_absolute(self):
        t = ResidualTracker()
        for meas in (1.1, 0.9, 1.1, 2.0):       # rels .1, -.1, .1, 1.0
            t.record(1.0, meas)
        assert t.drift() == pytest.approx(0.1)  # outlier-robust

    def test_bias_keeps_sign(self):
        t = ResidualTracker()
        for meas in (1.2, 1.3, 1.25):
            t.record(1.0, meas)
        assert t.bias() > 0.2
        t2 = ResidualTracker()
        for meas in (0.8, 0.7, 0.75):
            t2.record(1.0, meas)
        assert t2.bias() < -0.2

    def test_zero_predicted_is_safe(self):
        t = ResidualTracker()
        assert t.record(0.0, 1.0) == 0.0


# ---------------------------------------------------------------------------
# Empty-window contract: no sample can masquerade as a measurement
# ---------------------------------------------------------------------------
class TestEmptyWindowContract:
    def test_empty_ring_percentile_is_none(self):
        r = TimingRing(capacity=4)
        assert r.percentile(50.0) is None
        assert r.percentile(0.0) is None
        r.add(1.0)
        assert r.percentile(50.0) == 1.0
        r.reset()
        assert r.percentile(95.0) is None

    def test_empty_ring_summary_identity_fields(self):
        s = TimingRing(capacity=4).summary()
        assert s["count"] == 0 and s["total"] == 0
        assert s["mean"] == 0.0
        assert s["ewma"] is None and s["last"] is None
        assert s["p50"] is None and s["p95"] is None

    def test_empty_tracker_drift_and_bias_are_none(self):
        t = ResidualTracker()
        assert t.drift() is None
        assert t.bias() is None
        t.record(1.0, 1.5)
        assert t.drift() == pytest.approx(0.5)
        t.reset()
        assert t.drift() is None and t.bias() is None


# ---------------------------------------------------------------------------
# ArrivalEstimator
# ---------------------------------------------------------------------------
class TestArrivalEstimator:
    def test_offsets_relative_to_earliest(self):
        est = ArrivalEstimator()
        est.record([10.0, 10.5, 10.1, 10.0])
        assert est.n_devices == 4
        offs = est.offsets()
        assert offs[0] == 0.0
        assert offs[1] == pytest.approx(0.5)

    def test_median_over_collectives(self):
        est = ArrivalEstimator()
        for late in (0.1, 0.2, 0.3):
            est.record([0.0, late])
        assert est.count == 3
        assert est.offsets()[1] == pytest.approx(0.2)

    def test_reset(self):
        est = ArrivalEstimator()
        est.record([0.0, 1.0])
        est.reset()
        assert est.n_devices == 0 and est.count == 0


# ---------------------------------------------------------------------------
# Telemetry facade
# ---------------------------------------------------------------------------
class TestTelemetry:
    def test_rings_create_on_demand_and_share(self):
        tele = Telemetry()
        tele.record("train/step", 0.1)
        assert tele.ring("train/step").count == 1
        assert tele.ring("train/step") is tele.ring("train/step")

    def test_residual_and_sample_recording(self):
        tele = Telemetry()
        tele.record_residual("level/root_sw", 1.0, 1.4)
        assert tele.residuals("level/root_sw").drift() == pytest.approx(0.4)
        tele.record_sample("root_sw", LevelSample(8, 1e6, 0.01, 0.011))
        assert len(tele.samples("root_sw")) == 1
        tele.clear_samples("root_sw")
        assert tele.samples("root_sw") == []

    def test_remeasure_window_clears_suspect_state_keeps_rings(self):
        tele = Telemetry()
        tele.record("train/step", 0.1)
        tele.record_residual("level/root_sw", 1.0, 2.0)
        tele.record_sample("root_sw", LevelSample(8, 1e6, 0.01, 0.011))
        tele.record_arrivals([0.0, 0.5])
        tele.remeasure("remesh", {"dropped": 3})
        # residuals, samples, arrivals describe the old cluster: gone
        assert tele.residuals("level/root_sw").count == 0
        assert tele.samples("root_sw") == []
        assert tele.arrivals.n_devices == 0
        # raw timing rings survive for trend display; event logged
        assert tele.ring("train/step").count == 1
        assert [e.kind for e in tele.events] == ["remesh"]

    def test_stats_shape(self):
        tele = Telemetry()
        tele.record("x", 1.0)
        tele.record_residual("level/a", 1.0, 1.1)
        st = tele.stats()
        assert "x" in st["rings"] and "level/a" in st["residuals"]
        assert st["rings"]["x"]["count"] == 1


# ---------------------------------------------------------------------------
# CostLedger (DESIGN.md §11): per-term predicted seconds next to measured
# ---------------------------------------------------------------------------
def _entry(level="root_sw", predicted=1.0, measured=1.1, **shares):
    base = {"alpha": 0.0, "beta": 0.0, "gamma": 0.0, "delta": 0.0,
            "incast": 0.0}
    base.update(shares)
    return LedgerEntry(level=level, n=8, size_floats=1e6,
                       predicted=predicted, measured=measured, shares=base)


class TestCostLedger:
    def test_record_and_per_level_isolation(self):
        led = CostLedger()
        led.record(_entry(level="root_sw", alpha=0.4, beta=0.6))
        led.record(_entry(level="cross_dc", alpha=1.0))
        assert led.count("root_sw") == 1 and led.count("cross_dc") == 1
        assert led.levels() == ["cross_dc", "root_sw"]
        assert led.entries("nope") == []

    def test_totals_sum_terms_over_window(self):
        led = CostLedger()
        led.record(_entry(alpha=0.4, beta=0.6))
        led.record(_entry(alpha=0.1, beta=0.9))
        tot = led.totals("root_sw")
        assert tot["alpha"] == pytest.approx(0.5)
        assert tot["beta"] == pytest.approx(1.5)

    def test_bounded_window(self):
        led = CostLedger(capacity=3)
        for i in range(10):
            led.record(_entry(alpha=float(i)))
        assert led.count("root_sw") == 3
        assert [e.shares["alpha"] for e in led.entries("root_sw")] == \
            [7.0, 8.0, 9.0]

    def test_clear_level_and_all(self):
        led = CostLedger()
        led.record(_entry(level="a"))
        led.record(_entry(level="b"))
        led.clear("a")
        assert led.count("a") == 0 and led.count("b") == 1
        led.clear()
        assert led.levels() == []

    def test_remeasure_clears_ledger(self):
        tele = Telemetry()
        tele.ledger.record(_entry(alpha=1.0))
        tele.remeasure("remesh", {})
        assert tele.ledger.levels() == []

    def test_stats_reports_ledger_counts(self):
        tele = Telemetry()
        tele.ledger.record(_entry(level="root_sw"))
        assert tele.stats()["ledger"] == {"root_sw": 1}


# ---------------------------------------------------------------------------
# The shared datapath: watchdog EWMA lives in the telemetry ring
# ---------------------------------------------------------------------------
class TestWatchdogDatapath:
    def test_watchdog_writes_through_shared_ring(self):
        from repro.runtime import StragglerWatchdog
        tele = Telemetry()
        wd = StragglerWatchdog(threshold=2.0, halflife=5, telemetry=tele)
        for s in range(10):
            assert not wd.observe(s, 1.0)
        # the same samples are visible through the hub — one datapath
        ring = tele.ring("train/step")
        assert ring.count == 10 and ring.ewma == pytest.approx(1.0)
        assert wd.observe(10, 5.0)            # straggler
        assert ring.count == 11               # kept in window...
        assert ring.ewma == pytest.approx(1.0)  # ...but not the baseline
        assert wd.events and wd.events[0][0] == 10

    def test_ft_loop_straggler_opens_remeasure_window(self, tmp_path):
        import jax.numpy as jnp

        from repro.checkpoint import CheckpointManager
        from repro.runtime import FaultTolerantLoop, StragglerWatchdog

        tele = Telemetry()
        tele.record_residual("level/root_sw", 1.0, 2.0)
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        wd = StragglerWatchdog(threshold=2.0, halflife=5, telemetry=tele)
        # warm the first JAX dispatch OUTSIDE the loop: a cold step 0
        # would seed the watchdog EWMA with compile/dispatch time and a
        # small injected sleep could stay under 2x that baseline
        jnp.float32(0) + 1

        def step_fn(state, step):
            import time
            if step == 8:
                time.sleep(0.3)           # injected straggler, >> 2x
            return {"acc": state["acc"] + step}  # baseline even when cold

        loop = FaultTolerantLoop(step_fn, {"acc": jnp.float32(0)}, mgr,
                                 ckpt_every=100, watchdog=wd)
        assert loop.telemetry is tele     # one hub end to end
        loop.run(10)
        kinds = [e.kind for e in tele.events]
        assert "straggler" in kinds
        # pre-event residual history was dropped with the window
        assert tele.residuals("level/root_sw").count == 0

    def test_elastic_remesh_opens_remeasure_window(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh

        from repro.planner.service import PlannerService
        from repro.runtime import elastic_remesh

        tele = Telemetry()
        svc = PlannerService(telemetry=tele)
        svc.get_bucket_plan([("data", 8)], 4096.0)
        tele.record_sample("root_sw", LevelSample(8, 1e3, 0.01, 0.01))
        mesh = make_mesh((1,), ("data",))
        elastic_remesh({"w": jnp.ones((2,))},
                       {"w": NamedSharding(mesh, P())}, planner=svc)
        assert svc.executable_count() == 0
        assert [e.kind for e in tele.events] == ["remesh"]
        assert tele.samples("root_sw") == []
