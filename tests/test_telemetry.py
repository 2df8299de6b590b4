"""Continuous fleet telemetry (PR: time-series metrics, streaming anomaly
detection, SLO burn-rate alerting).

Pins this PR's contracts: the bounded ``TimeSeriesDB`` ring semantics and
JSONL round-trip, the OpenMetrics renderer/parser inverse pair, detector
unit behavior (robust z-score floors, storm hysteresis), multi-window SLO
burn + the admission-priority nudge, registry robustness
(``gauge_fn_errors_total``, ``drop``/``drop_labeled``), per-tenant label
GC in the admission queue, and the end-to-end in-band ``ALERT`` events —
deterministic under seeded chaos, absent on a clean corpus, validated by
the ``TraceChecker`` (invariant 9).
"""
import random
import time

import pytest

from repro.core import couler
from repro.core.analysis import TraceChecker
from repro.core.engines.cluster import Cluster, MultiClusterEngine
from repro.core.engines.local import LocalEngine
from repro.core.faults import FaultPlan, ReadmissionPolicy
from repro.core.gateway import AdmissionQueue, AdmittedItem, EventType
from repro.core.ir import Job, Resources, WorkflowIR
from repro.core.obs import MetricsRegistry
from repro.core.obs.anomaly import (AnomalyMonitor, ReadmissionStormDetector,
                                    StragglerDetector)
from repro.core.obs.exposition import parse_openmetrics, render_openmetrics
from repro.core.obs.slo import SLO, SLOMonitor
from repro.core.obs.timeseries import TimeSeriesDB


def _engine(**kw):
    kw.setdefault("enable_speculation", False)
    kw.setdefault("check_events", True)
    return LocalEngine(**kw)


def _chain_wf(name, n=2, fn=None):
    wf = WorkflowIR(name)
    prev = None
    for j in range(n):
        wf.add_job(Job(name=f"s{j}", fn=fn or (lambda j=j: j), cacheable=False))
        if prev:
            wf.add_edge(prev, f"s{j}")
        prev = f"s{j}"
    return wf


# ---------------------------------------------------------------- TimeSeriesDB

class TestTimeSeriesDB:
    def test_ring_bound_and_latest(self):
        db = TimeSeriesDB(capacity=4)
        for i in range(10):
            db.sample({"x": float(i)}, ts=float(i))
        assert db.samples_taken == 10
        pts = db.window("x", 100.0, now=10.0)
        assert len(pts) == 4                       # ring kept the last 4
        assert [v for _, v in pts] == [6.0, 7.0, 8.0, 9.0]
        assert db.latest("x") == 9.0
        assert db.latest("missing") is None
        assert db.latest_ts() == 9.0

    def test_counter_delta_and_rate(self):
        db = TimeSeriesDB()
        for ts, v in [(0.0, 0.0), (5.0, 10.0), (10.0, 30.0)]:
            db.sample({"c_total": v}, ts=ts)
        assert db.delta("c_total", 100.0, now=10.0) == 30.0
        assert db.rate("c_total", 100.0, now=10.0) == pytest.approx(3.0)
        # window excludes old points
        assert db.delta("c_total", 6.0, now=10.0) == 20.0
        # <2 points in window -> 0
        assert db.delta("c_total", 1.0, now=10.0) == 0.0

    def test_quantile_and_mean(self):
        db = TimeSeriesDB()
        for i in range(10):
            db.sample({"g": float(i)}, ts=float(i))
        assert db.quantile("g", 0.5) == 5.0
        assert db.quantile("g", 0.99) == 9.0
        assert db.mean("g", 100.0, now=9.0) == pytest.approx(4.5)
        assert db.quantile("nope", 0.5) == 0.0

    def test_histogram_flattening_and_skips(self):
        db = TimeSeriesDB()
        db.sample({"h": {"count": 3, "sum": 1.5, "buckets": {"1": 3}},
                   "flag": True, "s": "str", "v": 2}, ts=1.0)
        assert db.names() == ["h:count", "h:sum", "v"]
        assert db.latest("h:count") == 3.0
        assert db.latest("h:sum") == 1.5

    def test_jsonl_round_trip(self, tmp_path):
        live = tmp_path / "live.jsonl"
        db = TimeSeriesDB(path=str(live))
        for i in range(5):
            db.sample({"a": float(i), "b_total": float(2 * i)}, ts=float(i))
        # live-append file reloads identically
        back = TimeSeriesDB.load_jsonl(str(live))
        assert back.samples_taken == 5
        assert back.names() == db.names()
        assert back.latest("b_total") == 8.0
        # explicit export of the ring contents also round-trips
        dump = tmp_path / "dump.jsonl"
        assert db.export_jsonl(str(dump)) == 5
        again = TimeSeriesDB.load_jsonl(str(dump))
        assert again.latest("a") == 4.0


# ----------------------------------------------------------------- exposition

class TestExposition:
    def test_render_parse_round_trip(self):
        reg = MetricsRegistry()
        reg.counter("runs_total").inc(3)
        reg.counter("runs_total", tenant="a").inc(2)
        reg.gauge("depth").set(7)
        reg.histogram("lat_s", buckets=(0.1, 1.0)).observe(0.5)
        text = render_openmetrics(reg)
        assert text.endswith("# EOF\n")
        assert "# TYPE runs counter" in text
        assert "# TYPE depth gauge" in text
        assert "# TYPE lat_s histogram" in text
        parsed = parse_openmetrics(text)
        assert parsed["runs_total"] == 3.0
        assert parsed['runs_total{tenant="a"}'] == 2.0
        assert parsed["depth"] == 7.0
        assert parsed['lat_s_bucket{le="1.0"}'] == 1.0
        assert parsed["lat_s_count"] == 1.0
        assert parsed["lat_s_sum"] == 0.5

    def test_parse_rejects_malformed(self):
        with pytest.raises(ValueError, match="EOF"):
            parse_openmetrics("x 1\n")
        with pytest.raises(ValueError, match="malformed"):
            parse_openmetrics("not a sample line !!\n# EOF\n")
        with pytest.raises(ValueError, match="after # EOF"):
            parse_openmetrics("# EOF\nx 1\n")


# ------------------------------------------------------------------ detectors

class TestStragglerDetector:
    def test_fires_on_outlier_with_context(self):
        det = StragglerDetector()
        for k in range(10):
            assert det.note("w/s", 0.01 + 0.001 * k, ts=float(k)) is None
        a = det.note("w/s", 0.5, ts=11.0)
        assert a is not None and a.scope == "w/s"
        assert a.value > a.threshold == det.z_threshold
        # the context re-derives the crossing independently
        z = 0.6745 * (a.context["duration_s"] - a.context["median_s"]) \
            / a.context["scale_s"]
        assert z == pytest.approx(a.value)

    def test_cold_site_never_fires(self):
        det = StragglerDetector(min_samples=8)
        for k in range(7):
            assert det.note("cold/s", 0.01, ts=float(k)) is None
        assert det.note("cold/s", 99.0, ts=8.0) is None   # still < min_samples

    def test_duration_floor_suppresses_micro_jitter(self):
        det = StragglerDetector(min_duration_s=0.05)
        for k in range(10):
            det.note("fast/s", 0.001, ts=float(k))
        # z is huge (MAD floor) but 4ms is below the absolute floor
        assert det.note("fast/s", 0.004, ts=11.0) is None

    def test_median_ratio_floor(self):
        det = StragglerDetector(median_ratio=2.0)
        for k in range(10):
            det.note("slow/s", 0.1, ts=float(k))
        # 1.5x the median: not a straggler even though z clears threshold
        assert det.note("slow/s", 0.15, ts=11.0) is None
        assert det.note("slow/s", 0.25, ts=12.0) is not None

    def test_history_is_bounded(self):
        det = StragglerDetector(history=16)
        for k in range(100):
            det.note("b/s", 0.01, ts=float(k))
        assert len(det.site_history("b/s")) == 16


class TestReadmissionStormDetector:
    def test_hysteresis_one_alert_per_episode(self):
        det = ReadmissionStormDetector(window_s=10.0, threshold=3)
        assert det.note("w", "t", ts=1.0) is None
        assert det.note("w", "t", ts=2.0) is None
        a = det.note("w", "t", ts=3.0)
        assert a is not None and a.value == 3.0
        # still above threshold: armed, no repeat alert
        assert det.note("w", "t", ts=4.0) is None
        # window drains -> re-arms
        assert det.note("w", "t", ts=30.0) is None
        assert det.note("w", "t", ts=31.0) is None
        assert det.note("w", "t", ts=32.0) is not None


# ------------------------------------------------------------------------ SLO

class TestSLO:
    def test_validation(self):
        with pytest.raises(ValueError):
            SLO(tenant="x", completion_rate=1.5)
        with pytest.raises(ValueError):
            SLOMonitor([SLO(tenant="a"), SLO(tenant="a")])

    def test_multi_window_burn_fires_and_clears(self):
        mon = SLOMonitor([SLO(tenant="t", completion_rate=0.9)],
                         short_window_s=60.0, long_window_s=300.0,
                         burn_threshold=2.0, min_runs=5)
        now = 1000.0
        for i in range(10):          # 50% failures against a 10% budget
            mon.note_run("t", ok=(i % 2 == 0), ts=now - 30.0 + i)
        fired = mon.evaluate(now=now)
        assert len(fired) == 1
        a = fired[0]
        assert a.detector == "slo_burn" and a.scope == "t"
        assert a.context["burn_short"] == pytest.approx(5.0)
        assert a.context["burn_long"] == pytest.approx(5.0)
        assert mon.status(now=now)["t"]["burning"]
        # short window empties -> burn clears (min_runs gate)
        later = now + 120.0
        assert mon.evaluate(now=later) == []
        assert not mon.status(now=later)["t"]["burning"]

    def test_min_runs_gate(self):
        mon = SLOMonitor([SLO(tenant="t", completion_rate=0.9)], min_runs=5)
        now = 1000.0
        for i in range(3):
            mon.note_run("t", ok=False, ts=now - 1.0)
        assert mon.evaluate(now=now) == []

    def test_latency_objectives(self):
        mon = SLOMonitor([SLO(tenant="t", completion_rate=None,
                              p99_queue_wait_s=1.0,
                              makespan_budget_s=10.0)],
                         burn_threshold=2.0, min_runs=5)
        now = 1000.0
        for i in range(10):          # every run violates both bounds
            mon.note_run("t", ok=True, makespan_s=60.0, queue_wait_s=5.0,
                         ts=now - 10.0)
        fired = mon.evaluate(now=now)
        assert {a.reason.split("burning ")[1].split(" ")[0]
                for a in fired} == {"p99_queue_wait_s", "makespan_budget_s"}

    def test_nudge_boosts_then_restores_weight(self):
        q = AdmissionQueue(default_weight=1)
        q.weights["t"] = 2
        mon = SLOMonitor([SLO(tenant="t", completion_rate=0.9)],
                         burn_threshold=2.0, min_runs=5, nudge_factor=2,
                         max_weight=8)
        now = 1000.0
        for i in range(10):
            mon.note_run("t", ok=False, ts=now - 1.0)
        mon.evaluate(now=now)
        assert mon.nudge(q) == {"t": 4}            # 2 * nudge_factor
        assert q.weights["t"] == 4
        mon.evaluate(now=now + 120.0)              # burn cleared
        assert mon.nudge(q) == {"t": 2}            # base weight restored
        assert q.weights["t"] == 2


# ---------------------------------------------------------- registry hardening

class TestRegistryRobustness:
    def test_gauge_fn_errors_counted_not_fatal(self):
        reg = MetricsRegistry()
        reg.counter("good_total").inc()
        reg.gauge_fn("bad_gauge", lambda: 1 / 0)
        reg.gauge_fn("ok_gauge", lambda: 42.0)
        snap = reg.snapshot()
        assert snap["good_total"] == 1
        assert snap["ok_gauge"] == 42.0
        assert "bad_gauge" not in snap
        assert snap["gauge_fn_errors_total"] == 1
        assert reg.snapshot()["gauge_fn_errors_total"] == 2

    def test_drop_and_drop_labeled(self):
        reg = MetricsRegistry()
        reg.counter("c_total").inc()
        reg.counter("c_total", tenant="a").inc()
        reg.counter("c_total", tenant="b").inc()
        reg.gauge("d", tenant="a").set(3)
        assert reg.drop("c_total", tenant="a")
        assert not reg.drop("c_total", tenant="a")      # already gone
        assert reg.drop_labeled("tenant", "a") == 1     # the gauge
        snap = reg.snapshot()
        assert "c_total" in snap
        assert "c_total{tenant=b}" in snap
        assert "c_total{tenant=a}" not in snap
        assert "d{tenant=a}" not in snap


class TestAdmissionTenantGC:
    def test_idle_tenant_series_dropped(self):
        q = AdmissionQueue(tenant_retention_s=10.0)
        wf = _chain_wf("gc", n=1)
        q.offer(AdmittedItem(wf=wf, tenant="ghost"))
        assert q.pop() is not None
        assert "admission_depth{tenant=ghost}" in q.registry.snapshot()
        assert q.gc_idle_tenants(now=time.time() + 5.0) == []    # not idle yet
        doomed = q.gc_idle_tenants(now=time.time() + 60.0)
        assert doomed == ["ghost"]
        snap = q.registry.snapshot()
        assert not any("ghost" in k for k in snap)
        assert snap["admission_tenant_gc_total"] == 1

    def test_queued_tenant_survives_gc(self):
        q = AdmissionQueue(tenant_retention_s=10.0)
        q.offer(AdmittedItem(wf=_chain_wf("gc2", n=1), tenant="busy"))
        assert q.gc_idle_tenants(now=time.time() + 60.0) == []
        assert "admission_depth{tenant=busy}" in q.registry.snapshot()


# ---------------------------------------------------------------- integration

class TestInBandAlerts:
    def test_seeded_straggler_alert_is_deterministic(self):
        mon = AnomalyMonitor()
        for k in range(10):
            mon.straggler.note("tele/s1", 0.01 + 0.001 * k, ts=float(k))
        eng = _engine(
            max_workers=2,
            fault_plan=FaultPlan(seed=7, straggler_rate=1.0,
                                 straggler_delay_s=0.4,
                                 targets=frozenset({"s1"})),
            telemetry_interval_s=0.05, anomaly=mon)
        try:
            wf = _chain_wf("tele", n=2)
            h = eng.gateway.submit_nowait(wf, tenant="t0", block=True)
            run = h.result()
            assert run.succeeded()
            evs = h.events_so_far()
            checker = TraceChecker.check(evs, wf=wf)
            alerts = [e for e in evs if e.type is EventType.ALERT]
            assert len(alerts) == 1 == len(checker.alerts)
            assert alerts[0].status == "straggler"
            assert alerts[0].step == "s1"
            assert "z=" in alerts[0].error
            assert mon.counts() == {"straggler": 1}
            # the alert counter landed in the gateway-bound registry
            assert eng.gateway.registry.get_value(
                "alerts_total", detector="straggler") == 1
        finally:
            eng.close()

    def test_readmission_storm_alert_with_hysteresis(self):
        mon = AnomalyMonitor()
        eng = _engine(
            max_workers=2,
            fault_plan=FaultPlan(seed=5, permanent_rate=1.0,
                                 targets=frozenset({"s0"}),
                                 max_failures_per_site=3),
            readmission=ReadmissionPolicy(base_backoff_s=0.005,
                                          max_backoff_s=0.02),
            anomaly=mon)
        try:
            wf = _chain_wf("storm", n=1)
            h = eng.gateway.submit_nowait(wf, tenant="t1", block=True)
            run = h.result()
            assert run.succeeded()
            evs = h.events_so_far()
            TraceChecker.check(evs, wf=wf)
            req = [e for e in evs if e.type is EventType.WORKFLOW_REQUEUED]
            storm = [e for e in evs if e.type is EventType.ALERT]
            assert len(req) == 3
            assert len(storm) == 1          # hysteresis: once per episode
            assert storm[0].status == "readmission_storm"
        finally:
            eng.close()

    def test_clean_corpus_zero_false_positives(self):
        mon = AnomalyMonitor()
        slos = SLOMonitor([SLO(tenant=f"t{i}") for i in range(3)])
        eng = _engine(max_workers=4, telemetry_interval_s=0.02,
                      anomaly=mon, slo=slos)
        try:
            rng = random.Random(3)
            handles = []
            for i in range(24):
                wf = WorkflowIR(f"clean-{i}")
                n = rng.randint(2, 5)
                for j in range(n):
                    wf.add_job(Job(name=f"s{j}",
                                   fn=lambda: time.sleep(0.001),
                                   cacheable=False))
                for j in range(1, n):
                    for k in range(j):
                        if rng.random() < 0.4:
                            wf.add_edge(f"s{k}", f"s{j}")
                handles.append(eng.gateway.submit_nowait(
                    wf, tenant=f"t{i % 3}", block=True))
            runs = [h.result() for h in handles]
            assert all(r.succeeded() for r in runs)
            for h in handles:
                assert not any(e.type is EventType.ALERT
                               for e in h.events_so_far())
            assert len(mon.alerts) == 0
            assert len(slos.alerts) == 0
        finally:
            eng.close()


class TestTelemetryAPI:
    def test_couler_telemetry_samples_the_gateway(self):
        eng = _engine(max_workers=2)
        try:
            tsdb, mon, slo_mon = couler.telemetry(
                eng, interval_s=0.02, slos=[SLO(tenant="default")])
            assert isinstance(mon, AnomalyMonitor)
            assert isinstance(slo_mon, SLOMonitor)
            run = eng.submit(_chain_wf("tapi", n=3))
            assert run.succeeded()
            deadline = time.time() + 5.0
            while tsdb.samples_taken < 2 and time.time() < deadline:
                time.sleep(0.02)
            assert tsdb.samples_taken >= 2
            assert tsdb.latest("gateway_workflows_submitted_total") >= 1.0
            # slo monitor saw the finished run
            assert slo_mon.status()["default"]["runs_seen"] == 1
        finally:
            eng.close()

    def test_telemetry_requires_a_gateway(self):
        eng = MultiClusterEngine(clusters=[
            Cluster("a", cpu=8, mem_bytes=1 << 40)])
        with pytest.raises(TypeError, match="attach_telemetry"):
            couler.telemetry(eng)

    def test_cluster_attach_telemetry_samples_per_batch(self):
        eng = MultiClusterEngine(clusters=[
            Cluster("a", cpu=8, mem_bytes=1 << 40)])
        tsdb = TimeSeriesDB()
        eng.attach_telemetry(tsdb)
        wf = WorkflowIR("mc")
        wf.add_job(Job(name="j0", est_time_s=1.0, resources=Resources(cpu=2)))
        runs = eng.submit_many([(wf, "u0", 0)])
        assert all(r.succeeded() for r in runs.values())
        assert tsdb.samples_taken == 1
        assert tsdb.latest("cluster_workflows_total") is not None \
            or len(tsdb.names()) > 0

    def test_telemetry_jsonl_persistence(self, tmp_path):
        path = tmp_path / "tel.jsonl"
        eng = _engine(max_workers=2, telemetry_interval_s=0.02,
                      telemetry_path=str(path))
        try:
            run = eng.submit(_chain_wf("tpersist", n=2))
            assert run.succeeded()
            deadline = time.time() + 5.0
            while eng.gateway.tsdb.samples_taken < 2 \
                    and time.time() < deadline:
                time.sleep(0.02)
        finally:
            eng.close()
        back = TimeSeriesDB.load_jsonl(str(path))
        assert back.samples_taken >= 2
        assert back.latest("gateway_workflows_submitted_total") >= 1.0
