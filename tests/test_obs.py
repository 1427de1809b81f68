"""Tests for repro.obs: metrics registry, P² quantiles, tracing, drift."""
import json
import time

import numpy as np
import pytest

from repro.core import aie_arch
from repro.obs import (DEFAULT_PIDS, Counter, DriftMonitor, Gauge, Histogram,
                       MetricsRegistry, P2Quantile, Tracer)
from repro.obs.tracing import load as load_trace


class TestP2Quantile:
    @pytest.mark.parametrize("p", [0.5, 0.9, 0.99])
    @pytest.mark.parametrize("dist", ["uniform", "normal", "lognormal"])
    def test_accuracy_vs_numpy(self, p, dist):
        rng = np.random.default_rng(42)
        xs = {"uniform": lambda: rng.uniform(10.0, 1000.0, 20_000),
              "normal": lambda: rng.normal(500.0, 50.0, 20_000),
              "lognormal": lambda: rng.lognormal(3.0, 0.5, 20_000)}[dist]()
        est = P2Quantile(p)
        for x in xs:
            est.observe(float(x))
        exact = float(np.percentile(xs, 100 * p))
        assert abs(est.value - exact) / exact < 0.01

    def test_small_sample_interpolates(self):
        est = P2Quantile(0.5)
        for x in [1.0, 2.0, 3.0]:
            est.observe(x)
        assert est.value == 2.0

    def test_rejects_bad_p(self):
        with pytest.raises(ValueError):
            P2Quantile(0.0)
        with pytest.raises(ValueError):
            P2Quantile(1.0)


def _p2_reference(p, xs):
    """Textbook P² (Jain & Chlamtac 1985), one sample at a time over lists:
    the reference the estimator's batched update must match bit for bit.
    Returns (heights, positions, desired positions)."""
    q = sorted(xs[:5])
    n = [1.0, 2.0, 3.0, 4.0, 5.0]
    np_ = [1.0, 1.0 + 2.0 * p, 1.0 + 4.0 * p, 3.0 + 2.0 * p, 5.0]
    dn = [0.0, p / 2.0, p, (1.0 + p) / 2.0, 1.0]
    for x in xs[5:]:
        if x < q[0]:
            q[0], k = x, 0
        elif x >= q[4]:
            q[4], k = x, 3
        else:
            k = next(i for i in range(4) if q[i] <= x < q[i + 1])
        for i in range(k + 1, 5):
            n[i] += 1.0
        for i in range(5):
            np_[i] += dn[i]
        for i in (1, 2, 3):
            d = np_[i] - n[i]
            if ((d >= 1.0 and n[i + 1] - n[i] > 1.0)
                    or (d <= -1.0 and n[i - 1] - n[i] < -1.0)):
                d = 1.0 if d > 0 else -1.0
                qp = q[i] + d / (n[i + 1] - n[i - 1]) * (
                    (n[i] - n[i - 1] + d) * (q[i + 1] - q[i])
                    / (n[i + 1] - n[i])
                    + (n[i + 1] - n[i] - d) * (q[i] - q[i - 1])
                    / (n[i] - n[i - 1]))
                if not q[i - 1] < qp < q[i + 1]:
                    j = i + int(d)
                    qp = q[i] + d * (q[j] - q[i]) / (n[j] - n[i])
                q[i] = qp
                n[i] += d
    return q, n, np_


#: Batch splits: sizes cycled over the stream. "ragged" starts 2 + 4, so
#: the first five (buffered) samples straddle a batch boundary, as do 3's.
SPLITS = {"1": [1], "3": [3], "64": [64], "ragged": [2, 4, 1, 9, 64, 5, 33]}


def _chunks(xs, split):
    sizes, out, i, j = SPLITS[split], [], 0, 0
    while i < len(xs):
        k = sizes[j % len(sizes)]
        out.append(xs[i:i + k])
        i, j = i + k, j + 1
    return out


def _stream(n=4000, seed=11):
    """Heavy-tailed latencies rounded to 0.1 so values tie with markers,
    plus exact bucket bounds."""
    rng = np.random.default_rng(seed)
    xs = [round(float(v), 1) for v in rng.lognormal(4.0, 1.0, n)]
    xs[100:106] = [1.0, 2.0, 5.0, 100.0, 0.001, 1e12]
    return xs


class TestBatchedRecording:
    """``observe_many`` / ``record_many`` / drift ``observe_many`` leave the
    state that recording one value at a time leaves."""

    @pytest.mark.parametrize("split", list(SPLITS))
    @pytest.mark.parametrize("p", [0.5, 0.9, 0.99])
    def test_p2_observe_many_matches_per_sample(self, p, split):
        xs = _stream()
        batched = P2Quantile(p)
        for chunk in _chunks(xs, split):
            if split == "1":
                batched.observe(chunk[0])
            else:
                batched.observe_many(chunk)
        single = P2Quantile(p)
        for x in xs:
            single.observe(x)
        ref = _p2_reference(p, xs)
        assert (batched._q, batched._n, batched._np) == ref
        assert (single._q, single._n, single._np) == ref
        assert batched.value == single.value

    @pytest.mark.parametrize("split", list(SPLITS))
    def test_histogram_record_many_matches_per_sample(self, split):
        xs = _stream()
        batched, single = Histogram("lat", ()), Histogram("lat", ())
        for chunk in _chunks(xs, split):
            batched.record_many(chunk)
        for x in xs:
            single.record(x)
        assert batched.count == single.count == len(xs)
        assert batched.bucket_counts == single.bucket_counts
        # bucket i holds bounds[i-1] < x <= bounds[i]; the last, overflow
        expected = np.bincount(np.searchsorted(batched.bounds, xs,
                                               side="left"),
                               minlength=len(batched.bounds) + 1)
        assert batched.bucket_counts == expected.tolist()
        assert (batched.min, batched.max) == (single.min, single.max)
        assert batched.sum == pytest.approx(single.sum, rel=1e-9)
        for q, est in batched._p2.items():
            other = single._p2[q]
            assert (est._q, est._n, est._np) == (other._q, other._n,
                                                 other._np)

    def test_record_many_of_nothing_changes_nothing(self):
        h = Histogram("lat", ())
        h.record_many([])
        assert h.count == 0 and h.min is None and h.as_dict()["buckets"] == []

    @pytest.mark.parametrize("split", list(SPLITS))
    def test_drift_observe_many_matches_observe(self, split):
        xs = _stream(n=500)
        batched, single = DriftMonitor(), DriftMonitor()
        for chunk in _chunks(xs, split):
            batched.observe_many("a#0", "serve.latency_us", chunk)
        batched.observe_many("a#1", "serve.latency_us", [])
        for x in xs:
            single.observe("a#0", "serve.latency_us", x)
        [b], [s] = batched.entries(), single.entries()
        assert (b.key, b.count, b.last) == (s.key, s.count, s.last)
        assert b.count == len(xs) and b.last == xs[-1]
        assert b.total == pytest.approx(s.total, rel=1e-9)


class TestHistogram:
    def test_streaming_quantiles_vs_numpy(self):
        rng = np.random.default_rng(7)
        xs = rng.uniform(50.0, 5000.0, 20_000)
        h = Histogram("lat", ())
        for x in xs:
            h.record(float(x))
        for q in (0.5, 0.9, 0.99):
            exact = float(np.percentile(xs, 100 * q))
            assert abs(h.quantile(q) - exact) / exact < 0.01
        assert h.count == xs.size
        assert h.min == pytest.approx(xs.min())
        assert h.max == pytest.approx(xs.max())
        assert h.mean == pytest.approx(xs.mean())

    def test_bucket_counts_conserve(self):
        h = Histogram("x", ())
        for v in [0.5, 3.0, 42.0, 1e6, 1e12]:   # incl. +Inf overflow
            h.record(v)
        assert sum(h.bucket_counts) == h.count == 5
        assert h.bucket_counts[-1] == 1          # 1e12 beyond last bound

    def test_merge_adds_and_falls_back_to_buckets(self):
        rng = np.random.default_rng(3)
        xs = rng.uniform(100.0, 1000.0, 10_000)
        a, b = Histogram("m", ()), Histogram("m", ())
        for x in xs[:5000]:
            a.record(float(x))
        for x in xs[5000:]:
            b.record(float(x))
        a.merge(b)
        assert a.count == xs.size
        assert a.sum == pytest.approx(xs.sum())
        # P² state is dropped on merge; quantile() must still answer from
        # the merged buckets, within bucket resolution.
        assert a.quantile(0.5) == a.bucket_quantile(0.5)
        exact = float(np.percentile(xs, 50))
        assert abs(a.quantile(0.5) - exact) / exact < 0.15

    def test_merge_rejects_mismatched_buckets(self):
        a = Histogram("m", (), buckets=[1.0, 2.0])
        b = Histogram("m", (), buckets=[1.0, 3.0])
        with pytest.raises(ValueError):
            a.merge(b)


class TestRegistry:
    def test_get_or_create_and_label_order(self):
        reg = MetricsRegistry()
        c1 = reg.counter("hits", {"a": 1, "b": 2})
        c2 = reg.counter("hits", {"b": 2, "a": 1})
        assert c1 is c2
        c1.inc(3)
        assert reg.find("hits", {"b": 2, "a": 1}).value == 3

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_json_round_trip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("events", {"tenant": "a"}).inc(7)
        reg.gauge("depth").set(3.5)
        h = reg.histogram("lat_us")
        for v in (10.0, 20.0, 30.0):
            h.record(v)
        snap = json.loads(reg.to_json())
        assert snap["counters"][0]["value"] == 7
        assert snap["gauges"][0]["value"] == 3.5
        assert snap["histograms"][0]["count"] == 3
        p = tmp_path / "m.json"
        reg.save(str(p), extra={"run": "t"})
        on_disk = json.loads(p.read_text())
        assert on_disk["run"] == "t"
        assert on_disk["counters"] == snap["counters"]

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("fleet.dispatched", {"tenant": "a"}).inc(4)
        h = reg.histogram("lat.us", buckets=[1.0, 10.0])
        for v in (0.5, 5.0, 50.0):
            h.record(v)
        text = reg.to_prometheus()
        assert '# TYPE fleet_dispatched counter' in text
        assert 'fleet_dispatched{tenant="a"} 4' in text
        # cumulative buckets: le=1 -> 1, le=10 -> 2, +Inf -> 3
        assert 'lat_us_bucket{le="1"} 1' in text
        assert 'lat_us_bucket{le="10"} 2' in text
        assert 'lat_us_bucket{le="+Inf"} 3' in text
        assert 'lat_us_count 3' in text

    def test_merge_registries(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("n").inc(2)
        b.counter("n").inc(5)
        a.gauge("g").set(1.0)
        b.gauge("g").set(9.0)
        b.gauge("g").set(8.0)        # more writes -> b wins
        for v in (1.0, 2.0):
            a.histogram("h").record(v)
        for v in (3.0, 4.0):
            b.histogram("h").record(v)
        a.merge(b)
        assert a.find("n").value == 7
        assert a.find("g").value == 8.0
        assert a.find("h").count == 4
        assert a.find("h").sum == pytest.approx(10.0)


class TestTracer:
    def test_lanes_and_metadata(self):
        tr = Tracer()
        tr.span_us("tiles", "t0", "task", 0.0, 5.0)
        tr.span_us("tiles", "t1", "task", 1.0, 5.0)
        tr.span_us("dse", "m", "dp", 0.0, 2.0)
        assert tr.pid("tiles") == DEFAULT_PIDS["tiles"]
        assert tr.pid("dse") == DEFAULT_PIDS["dse"]
        names = [e["args"]["name"] for e in tr.events
                 if e["ph"] == "M" and e["name"] == "process_name"]
        assert names == ["tiles", "dse"]
        assert len(tr.spans("tiles")) == 2
        assert len(tr.spans()) == 3

    def test_new_pid_allocates_beyond_defaults(self):
        tr = Tracer()
        assert tr.pid("custom") > max(DEFAULT_PIDS.values())
        assert tr.pid("custom") == tr.pid("custom")

    def test_region_nesting(self):
        tr = Tracer()
        with tr.region("dse", "m", "outer"):
            with tr.region("dse", "m", "inner"):
                time.sleep(0.001)
        spans = {e["name"]: e for e in tr.spans("dse")}
        o, i = spans["outer"], spans["inner"]
        assert o["ts"] <= i["ts"]
        assert o["ts"] + o["dur"] >= i["ts"] + i["dur"]

    def test_save_load_round_trip(self, tmp_path):
        tr = Tracer(meta={"run": "t"})
        tr.span_us("events", "e0", "ev", 1.0, 2.0, cat="c", args={"k": 1})
        p = tmp_path / "trace.json"
        tr.save(str(p))
        data = load_trace(str(p))
        assert data["otherData"]["run"] == "t"
        xs = [e for e in data["traceEvents"] if e["ph"] == "X"]
        assert xs[0]["name"] == "ev" and xs[0]["cat"] == "c"

    def test_load_rejects_negative_span(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"traceEvents": [
            {"ph": "X", "name": "x", "pid": 1, "tid": 1,
             "ts": -1.0, "dur": 2.0}]}))
        with pytest.raises(ValueError):
            load_trace(str(p))

    def test_chrome_trace_cycle_conversion(self):
        from repro.sim.trace import ChromeTrace
        tr = ChromeTrace()
        tr.span("tiles", "t0", "mm", 0.0, 1250.0)   # 1250 cy @ 1.25 GHz = 1 us
        sp = tr.spans("tiles")[0]
        assert sp["dur"] == pytest.approx(1250.0 * aie_arch.NS_PER_CYCLE
                                          / 1000.0)


class TestDriftMonitor:
    def test_ratio_and_mape(self):
        mon = DriftMonitor()
        mon.expect("a#0", "serve.latency_us", 100.0)
        for v in (98.0, 102.0):
            mon.observe("a#0", "serve.latency_us", v)
        assert mon.ratio("a#0", "serve.latency_us") == pytest.approx(1.0)
        assert mon.mape("serve.latency_us") == pytest.approx(0.0)

    def test_flags_inflated_replica(self):
        mon = DriftMonitor()
        for key, measured in [("a#0", 100.0), ("a#1", 150.0)]:
            mon.expect(key, "serve.latency_us", 100.0)
            mon.observe(key, "serve.latency_us", measured)
        bad = mon.flagged(0.2, "serve.latency_us")
        assert [e.key for e in bad] == ["a#1"]
        assert bad[0].ratio == pytest.approx(1.5)
        assert mon.mape("serve.latency_us") == pytest.approx(0.25)

    def test_observe_before_expect_is_unpopulated(self):
        mon = DriftMonitor()
        mon.observe("k", "m", 5.0)
        assert mon.ratio("k", "m") is None
        assert mon.mape() is None
        s = mon.summary()
        assert s["m"]["entries"]["k"]["measured"] == 5.0
        assert s["m"]["entries"]["k"]["ratio"] is None


class TestDSETelemetry:
    def test_explore_records_counters_and_spans(self):
        from repro.core import dse, layerspec
        reg, tr = MetricsRegistry(), Tracer()
        best = dse.explore(layerspec.jsc_m(), registry=reg, tracer=tr)
        assert best is not None
        evald = reg.find("dse.candidates_evaluated", {"model": "JSC-M"})
        assert evald is not None and evald.value > 0
        phases = {e["name"] for e in tr.spans("dse")}
        assert {"dp", "score"} <= phases
        walltimes = reg.all("dse.walltime_s")
        assert walltimes and all(g.value >= 0 for g in walltimes)


class TestSimTelemetry:
    @pytest.fixture(scope="class")
    def res(self):
        from repro.core import dse, layerspec
        from repro.sim import run as simrun
        design = dse.explore(layerspec.jsc_m())
        return simrun.simulate_placement(
            design.placement, tenant="jsc-m",
            config=simrun.SimConfig(events=2, trace=False))

    def test_export_metrics(self, res):
        reg = res.export_metrics()
        utils = reg.all("sim.resource.utilization")
        assert utils and all(0.0 <= g.value <= 1.0 for g in utils)
        bottlenecks = reg.all("sim.bottleneck.utilization")
        assert len(bottlenecks) == 1
        assert bottlenecks[0].value == pytest.approx(
            max(g.value for g in utils))
        lat = reg.all("sim.event.latency_ns")
        assert lat and lat[0].count == 2
        assert lat[0].mean == pytest.approx(res.latency_ns)

    def test_unified_timeline_sim_plus_wall(self):
        """One ChromeTrace carries cycle-clock sim spans AND wall-clock
        DSE phase spans."""
        from repro.core import dse, layerspec
        from repro.sim import run as simrun
        from repro.sim.trace import ChromeTrace
        tr = ChromeTrace(meta={"test": "unified"})
        design = dse.explore(layerspec.jsc_m())
        simrun.simulate_placement(design.placement, tenant="jsc-m",
                                  config=simrun.SimConfig(events=1),
                                  tracer=tr)
        with tr.region("dse", "jsc-m", "score"):
            pass
        lanes = {e["args"]["name"] for e in tr.events
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert "tiles" in lanes and "dse" in lanes
        assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in tr.spans())


class TestHeavyTailedLoad:
    """obs.metrics under bursty, high-CV open-loop streams: the quantile
    estimators and exporters backing the ``slo.*``/``load.*`` families."""

    def _bursty_gaps(self, cv, n=20_000, seed=9):
        from repro.serve import workload
        ts = workload.arrival_times(workload.burst(1e6, cv), n + 1,
                                    seed=seed)
        return np.diff(np.asarray(ts)) * 1e9        # inter-arrival gaps, ns

    @pytest.mark.parametrize("cv", [2.0, 4.0])
    def test_p2_quantiles_on_bursty_stream(self, cv):
        xs = self._bursty_gaps(cv)
        for p in (0.5, 0.9, 0.99):
            est = P2Quantile(p)
            for x in xs:
                est.observe(float(x))
            exact = float(np.percentile(xs, 100 * p))
            assert abs(est.value - exact) / exact < 0.05, (cv, p)

    def test_histogram_merge_on_bursty_shards(self):
        """Per-replica histograms merged into a fleet view must preserve
        counts, sum, and tail quantiles on a high-CV stream."""
        xs = self._bursty_gaps(4.0)
        shards = [Histogram("w", ()) for _ in range(4)]
        for i, x in enumerate(xs):
            shards[i % 4].record(float(x))
        total = Histogram("w", ())
        for s in shards:
            total.merge(s)
        assert total.count == xs.size
        assert total.sum == pytest.approx(xs.sum())
        assert total.max == pytest.approx(xs.max())
        exact_p99 = float(np.percentile(xs, 99))
        # merge falls back to bucket interpolation -> coarser than P²
        assert abs(total.quantile(0.99) - exact_p99) / exact_p99 < 0.25

    def test_slo_and_load_families_round_trip(self, tmp_path):
        """slo.* / load.* / model.queue.* metrics survive JSON and
        Prometheus export intact."""
        from repro.obs.slo import SLOSpec, SLOTracker
        reg = MetricsRegistry()
        tr = SLOTracker(SLOSpec(tenant="a", p99_latency_budget_ns=1000.0,
                                availability=0.99, window_s=60.0),
                        registry=reg)
        for i in range(20):
            tr.record(2000.0 if i % 4 == 0 else 100.0, t=i * 0.1)
        tr.snapshot(now=2.0)
        reg.counter("load.offered", {"tenant": "a"}).inc(25)
        reg.counter("load.admitted", {"tenant": "a"}).inc(20)
        reg.counter("load.shed", {"tenant": "a"}).inc(5)
        reg.gauge("model.queue.sojourn_p99_ns", {"model": "m"}).set(1234.5)
        snap = json.loads(reg.to_json())
        counters = {(c["name"], c["labels"].get("tenant")): c["value"]
                    for c in snap["counters"]}
        assert counters[("slo.requests.good", "a")] == 15
        assert counters[("slo.requests.bad", "a")] == 5
        assert counters[("load.offered", "a")] == 25
        assert counters[("load.shed", "a")] == 5
        gauges = {g["name"]: g["value"] for g in snap["gauges"]}
        assert gauges["model.queue.sojourn_p99_ns"] == 1234.5
        assert "slo.error_budget.remaining" in gauges
        text = reg.to_prometheus()
        assert 'load_offered{tenant="a"} 25' in text
        assert 'slo_requests_bad{tenant="a"} 5' in text
        assert 'model_queue_sojourn_p99_ns{model="m"} 1234.5' in text

    def test_drift_summary_carries_flagged_and_suspects(self):
        mon = DriftMonitor()
        mon.expect("k1", "model.queue.sojourn_p99_ns", 100.0)
        mon.observe("k1", "model.queue.sojourn_p99_ns", 200.0)
        mon.expect("k2", "model.queue.sojourn_p99_ns", 100.0)
        mon.observe("k2", "model.queue.sojourn_p99_ns", 101.0)
        s = mon.summary(flag_threshold=0.10)
        d = s["model.queue.sojourn_p99_ns"]
        assert d["flagged"] == ["k1"]
        mon.expect("a#0", "model.stage.shim", 100.0)
        mon.observe("a#0", "model.stage.shim", 300.0)
        s2 = mon.summary(flag_threshold=0.10)
        assert s2["model.stage.shim"]["suspects"], \
            "flagged stage metric must name suspect constants"
