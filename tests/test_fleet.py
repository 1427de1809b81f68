"""Fleet serving engine (repro.serve.fleet): dispatch policies, replica
accounting, merged stats, and the ServeStats edge-case fixes."""
import time

import jax
import numpy as np
import pytest

from repro.data import JetConfig, jet_batch
from repro.models import mlp as mlp_lib
from repro.serve import ServeStats
from repro.serve.fleet import FleetServer, TenantSpec


@pytest.fixture(scope="module")
def qmlp():
    jc = JetConfig(n_particles=16, n_features=8, n_classes=5, seed=0)
    params = mlp_lib.mlp_init(jax.random.key(0), 8, [16, 16, 5])
    xcal, _ = jet_batch(jc, 64, 1)
    return mlp_lib.to_quantized(params, xcal), jc


def _events(jc, n, e_in, seed=7):
    x, _ = jet_batch(jc, n, seed)
    return np.clip(np.round(x / 2.0 ** e_in), -128, 127).astype(np.int8)


class TestServeStats:
    def test_empty(self):
        s = ServeStats()
        assert s.percentile(99) == 0.0
        assert s.throughput_eps() == 0.0
        assert s.summary()["throughput_eps"] == 0.0

    def test_small_sample_tail_is_max(self):
        s = ServeStats()
        for lat in (10.0, 20.0, 30.0, 1000.0):
            s.latencies_us.append(lat)
        # 4 samples: interpolated p99 would sit below the observed max
        assert s.percentile(99) == 1000.0
        assert s.percentile(50) == pytest.approx(25.0)

    def test_large_sample_tail_interpolates(self):
        s = ServeStats()
        s.latencies_us.extend(float(i) for i in range(1, 202))
        assert s.percentile(99) < 201.0
        assert s.percentile(99) > 195.0

    def test_record_window_and_throughput(self):
        s = ServeStats()
        t0 = time.perf_counter()
        for i in range(10):
            s.record(t0 + i * 0.01, t0 + i * 0.01 + 0.005)
        assert s.t_first_submit == pytest.approx(t0)
        assert s.t_last_done == pytest.approx(t0 + 0.095)
        assert s.throughput_eps() == pytest.approx(10 / 0.095, rel=1e-6)
        assert s.summary()["throughput_eps"] > 0


class TestFleetServer:
    def test_round_robin_accounting(self, qmlp):
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=3)], policy="rr")
        try:
            xs = _events(jc, 12, q.e_in)
            for i in range(12):
                fleet.infer(xs[i])
            counts = fleet.replica_counts("m")
            assert counts == [4, 4, 4]
        finally:
            fleet.close()

    def test_least_loaded_total_accounting(self, qmlp):
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=4)], policy="least_loaded")
        try:
            xs = _events(jc, 20, q.e_in)
            reqs = [fleet.submit(xs[i]) for i in range(20)]
            for r in reqs:
                assert r.event.wait(30)
            counts = fleet.replica_counts("m")
            assert sum(counts) == 20
            assert len(counts) == 4
        finally:
            fleet.close()

    def test_results_match_single_server(self, qmlp):
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=2)])
        single = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                         replicas=1)])
        try:
            xs = _events(jc, 6, q.e_in)
            for i in range(6):
                a = fleet.infer(xs[i])
                b = single.infer(xs[i])
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        finally:
            fleet.close()
            single.close()

    def test_merged_stats_and_summary(self, qmlp):
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=2)])
        try:
            xs = _events(jc, 8, q.e_in)
            for i in range(8):
                fleet.infer(xs[i])
            st = fleet.stats("m")
            assert len(st.latencies_us) == 8
            assert st.percentile(50) > 0
            assert st.throughput_eps() > 0
            s = fleet.summary()
            assert s["fleet"]["n"] == 8
            assert s["fleet"]["replicas"] == 2
            assert s["tenants"]["m"]["dispatched"] and \
                sum(s["tenants"]["m"]["dispatched"]) == 8
        finally:
            fleet.close()

    def test_multi_tenant_routing(self, qmlp):
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="a", qmlp=q, mode="ref",
                                        replicas=1),
                             TenantSpec(name="b", qmlp=q, mode="ref",
                                        replicas=2)])
        try:
            xs = _events(jc, 6, q.e_in)
            for i in range(4):
                fleet.infer(xs[i], tenant="a")
            for i in range(6):
                fleet.infer(xs[i], tenant="b")
            assert sum(fleet.replica_counts("a")) == 4
            assert sum(fleet.replica_counts("b")) == 6
            # tenant=None covers the whole fleet, matching stats(None)
            assert sum(fleet.replica_counts()) == 10
            assert len(fleet.replica_counts()) == 3
            assert fleet.stats().summary()["n"] == 10
            assert fleet.num_replicas == 3
            with pytest.raises(KeyError):
                fleet.submit(xs[0], tenant="nope")
        finally:
            fleet.close()

    def test_infer_batch_scatter_gather(self, qmlp):
        """Micro-batched dispatch: results in submission order and equal to
        per-event dispatch; the scatter covers every replica."""
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=3)])
        single = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                         replicas=1)])
        try:
            xs = _events(jc, 9, q.e_in)
            br = fleet.infer_batch(xs)
            assert br.results.shape[0] == 9
            assert br.n == 9
            assert br.replica_counts == [3, 3, 3]
            assert sum(fleet.replica_counts("m")) == 9
            assert br.percentile(50) > 0 and br.percentile(99) > 0
            assert br.throughput_eps > 0
            assert br.summary()["n"] == 9
            for i in range(9):
                np.testing.assert_array_equal(
                    np.asarray(br.results[i]), np.asarray(single.infer(xs[i])))
        finally:
            fleet.close()
            single.close()

    def test_infer_batch_smaller_than_fleet(self, qmlp):
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=4)])
        try:
            xs = _events(jc, 2, q.e_in)
            br = fleet.infer_batch(xs)
            assert br.n == 2 and br.results.shape[0] == 2
            assert sum(br.replica_counts) == 2
            assert fleet.submit_batch([]) == []
            empty = fleet.infer_batch([])
            assert empty.n == 0 and empty.results.shape[0] == 0
            assert empty.replica_counts == [0, 0, 0, 0]
            with pytest.raises(KeyError):
                fleet.submit_batch(xs, tenant="nope")
            with pytest.raises(KeyError):
                fleet.infer_batch(xs, tenant="nope")
        finally:
            fleet.close()

    def test_bad_args(self, qmlp):
        q, _ = qmlp
        with pytest.raises(ValueError):
            FleetServer([])
        with pytest.raises(ValueError):
            FleetServer([TenantSpec(name="m", qmlp=q, replicas=0)])
        with pytest.raises(ValueError):
            FleetServer([TenantSpec(name="m", qmlp=q)], policy="magic")
        with pytest.raises(ValueError):
            FleetServer([TenantSpec(name="m", qmlp=q),
                         TenantSpec(name="m", qmlp=q)])

    def test_model_error_reaches_caller(self, qmlp):
        """A replica whose model function raises (say, a kernel compile
        error) hands that exception to every waiter at once; the batch
        raises it instead of waiting out its timeout, and the worker keeps
        serving."""
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=2)])
        err = RuntimeError("Mosaic failed to compile TPU kernel")

        def broken(xs):
            raise err

        try:
            fns = [s._fn for s in fleet._servers["m"]]
            for s in fleet._servers["m"]:
                s._fn = broken
            xs = _events(jc, 6, q.e_in)
            t0 = time.perf_counter()
            with pytest.raises(RuntimeError) as info:
                fleet.infer_batch(xs, timeout=30)
            assert info.value is err
            with pytest.raises(RuntimeError) as info:
                fleet.infer(xs[0], timeout=30)
            assert info.value is err
            assert time.perf_counter() - t0 < 10
            for s, fn in zip(fleet._servers["m"], fns):
                s._fn = fn
            assert fleet.infer_batch(xs, timeout=30).n == 6
        finally:
            fleet.close()


class TestFleetTelemetry:
    def test_dispatch_metrics_recorded(self, qmlp):
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=2)])
        try:
            xs = _events(jc, 8, q.e_in)
            fleet.infer_batch(xs)
            for i in range(4):
                fleet.infer(xs[i])
            reg = fleet.registry
            disp = reg.all("fleet.replica.dispatched")
            assert sum(c.value for c in disp) == 12
            depths = reg.all("fleet.replica.queue_depth")
            assert len(depths) == 2
            lat = reg.find("fleet.request.latency_us", {"tenant": "m"})
            assert lat is not None and lat.count == 12
            assert lat.quantile(0.5) > 0
            assert reg.find("fleet.batch.size", {"tenant": "m"}).count == 1
            oh = reg.find("fleet.dispatch.overhead_us", {"tenant": "m"})
            assert oh is not None and oh.count == 5   # 1 batch + 4 singles
            s = fleet.summary()["tenants"]["m"]
            assert s["rolling_p50_us"] > 0
            assert s["rolling_p99_us"] >= s["rolling_p50_us"]
        finally:
            fleet.close()

    def test_adaptive_scatter_skews_away_from_backlog(self, qmlp):
        """A replica with a queue backlog gets a proportionally smaller
        slice; equal queues reduce to the balanced split."""
        q, _ = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=2)])
        try:
            servers = fleet._servers["m"]
            # Freeze the workers so the staged backlog is stable.
            for s in servers:
                s._stop.set()
            for s in servers:
                s._thread.join(timeout=5)
            assert [len(ix) for ix in fleet._slices("m", 10)] == [5, 5]
            for _ in range(4):
                servers[0]._q.put(object())
            # weights 1/5 vs 1 -> shares [1.67, 8.33] -> [2, 8]
            assert [len(ix) for ix in fleet._slices("m", 10)] == [2, 8]
            # slices stay contiguous and cover the batch in order
            np.testing.assert_array_equal(
                np.concatenate(fleet._slices("m", 10)), np.arange(10))
        finally:
            fleet.close()

    def test_drift_snapshot_and_telemetry(self, qmlp):
        import json as _json

        from repro.core import layerspec
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=2,
                                        model_spec=layerspec.jsc_m())])
        try:
            xs = _events(jc, 8, q.e_in)
            fleet.infer_batch(xs)
            snap = fleet.telemetry_snapshot(tier_s=True)
        finally:
            fleet.close()
        drift = snap["drift"]
        # serving path: per-replica ratios populated, hugely inflated vs
        # the modeled VEK280 (CPU interpret mode) — informational only
        entries = drift["serve.latency_us"]["entries"]
        assert set(entries) == {"m#0", "m#1"}
        assert all(e["ratio"] is not None and e["ratio"] > 1.0
                   for e in entries.values())
        # model path: Tier-A analytic vs Tier-S simulated, tight agreement
        model = drift["model.latency_ns"]["entries"]["m"]
        assert model["ratio"] == pytest.approx(1.0, abs=0.05)
        assert drift["model.latency_ns"]["mape"] < 0.05
        _json.dumps(snap)   # whole bundle must be JSON-serializable
        assert fleet.drift.flagged(10.0, "serve.latency_us")


    def test_batched_completion_telemetry_counts_every_event(self, qmlp):
        """Mixed batches through two replicas: every served event lands
        once in each histogram, completion counter and drift entry."""
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=2)])
        try:
            xs = _events(jc, 40, q.e_in)
            fleet.infer_batch(xs[:17])
            for i in range(17, 21):
                fleet.infer(xs[i])
            fleet.infer_batch(xs[21:])
            n = len(xs)
            reg = fleet.registry
            assert sum(fleet.stats("m").batch_sizes) == n
            assert max(fleet.stats("m").batch_sizes) > 1
            for name in ("fleet.request.latency_us",
                         "fleet.request.queue_wait_us"):
                h = reg.find(name, {"tenant": "m"})
                assert h.count == sum(h.bucket_counts) == n
            done = reg.all("fleet.replica.completed")
            assert len(done) == 2 and sum(c.value for c in done) == n
            entries = fleet.drift.entries("serve.latency_us")
            assert {e.key for e in entries} <= {"m#0", "m#1"}
            assert sum(e.count for e in entries) == n
        finally:
            fleet.close()

    def test_batch_observer_runs_once_before_waiters_wake(self, qmlp):
        """``on_batch`` sees each served batch once, with every answer set
        and no waiter woken; one that raises still answers every waiter."""
        from repro.serve import JetServer
        q, jc = qmlp
        calls = []

        def observer(batch):
            calls.append((list(batch),
                          all(r.result is not None for r in batch),
                          any(r.event.is_set() for r in batch)))
            raise RuntimeError("observer bug")

        srv = JetServer(q, mode="ref", max_batch=8, window_us=50_000,
                        on_batch=observer)
        ref = JetServer(q, mode="ref")
        try:
            xs = _events(jc, 12, q.e_in)
            reqs = [srv.submit(x) for x in xs]
            for r, x in zip(reqs, xs):
                np.testing.assert_array_equal(r.wait(30), ref.infer(x))
            assert [len(b) for b, _, _ in calls] == srv.stats.batch_sizes
            assert ([id(r) for b, _, _ in calls for r in b]
                    == [id(r) for r in reqs])
            assert all(answered and not woken
                       for _, answered, woken in calls)
        finally:
            srv.close()
            ref.close()


class TestLoadAndSLO:
    """Open-loop ingress: offer/shed accounting, SLO trackers, and the
    workload driver against a real (ref-mode) fleet."""

    def test_offer_admits_all_without_depth(self, qmlp):
        q, jc = qmlp
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=2)])
        try:
            xs = _events(jc, 8, q.e_in)
            reqs = [fleet.offer(xs[i]) for i in range(8)]
            assert all(r is not None for r in reqs)
            for r in reqs:
                assert r.event.wait(timeout=30)
            reg = fleet.registry
            assert reg.find("load.offered", {"tenant": "m"}).value == 8
            assert reg.find("load.admitted", {"tenant": "m"}).value == 8
            assert reg.find("load.shed", {"tenant": "m"}).value == 0
            with pytest.raises(KeyError):
                fleet.offer(xs[0], tenant="ghost")
        finally:
            fleet.close()

    def test_offer_sheds_at_admission_depth(self, qmlp):
        q, jc = qmlp
        from repro.obs.slo import SLOSpec
        slo = SLOSpec(tenant="m", p99_latency_budget_ns=1e6,
                      availability=0.9, window_s=60.0)
        # depth 0: every replica queue is always "full" -> shed everything
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=1)],
                            slos={"m": slo}, admission_depth=0)
        try:
            xs = _events(jc, 5, q.e_in)
            assert all(fleet.offer(xs[i]) is None for i in range(5))
            reg = fleet.registry
            assert reg.find("load.offered", {"tenant": "m"}).value == 5
            assert reg.find("load.admitted", {"tenant": "m"}).value == 0
            assert reg.find("load.shed", {"tenant": "m"}).value == 5
            tr = fleet.slo_trackers["m"]
            assert tr.shed == 5
            rep = fleet.slo_snapshot()
            assert rep.tenants["m"]["shed"] == 5
            assert rep.meta["admission_depth"] == 0
        finally:
            fleet.close()

    def test_slo_validation_at_construction(self, qmlp):
        q, _ = qmlp
        from repro.obs.slo import SLOSpec
        spec = SLOSpec(tenant="ghost", p99_latency_budget_ns=1e6,
                       availability=0.99, window_s=60.0)
        with pytest.raises(ValueError, match="unknown tenant"):
            FleetServer([TenantSpec(name="m", qmlp=q, mode="ref")],
                        slos={"ghost": spec})
        with pytest.raises(ValueError, match="names tenant"):
            FleetServer([TenantSpec(name="m", qmlp=q, mode="ref")],
                        slos={"m": spec})

    def test_completion_feeds_slo_and_queue_wait(self, qmlp):
        q, jc = qmlp
        from repro.obs.slo import SLOSpec
        slo = SLOSpec(tenant="m", p99_latency_budget_ns=1e12,
                      availability=0.9, window_s=60.0)
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=2)], slos={"m": slo})
        try:
            xs = _events(jc, 10, q.e_in)
            reqs = [fleet.offer(xs[i]) for i in range(10)]
            for r in reqs:
                assert r.event.wait(timeout=30)
            # generous 1 ms p99 budget in ns -> every request is good
            tr = fleet.slo_trackers["m"]
            assert tr.good == 10 and tr.bad == 0
            wait = fleet.registry.find("fleet.request.queue_wait_us",
                                       {"tenant": "m"})
            assert wait is not None and wait.count == 10
            assert wait.min >= 0.0
            snap = fleet.telemetry_snapshot(drift=False)
            assert snap["slo"]["tenants"]["m"]["good"] == 10
            assert snap["slo"]["ok"] is True
        finally:
            fleet.close()

    def test_workload_drive_on_real_fleet(self, qmlp):
        q, jc = qmlp
        from repro.serve import workload
        fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                        replicas=2)])
        try:
            xs = _events(jc, 16, q.e_in)
            dr = workload.drive(fleet, list(xs), workload.poisson(2000.0),
                                tenant="m", seed=1)
            assert dr.offered == 16
            assert dr.admitted == 16 and dr.shed == 0
            assert dr.admitted_idx == list(range(16))
            for r in dr.requests:
                assert r.event.wait(timeout=30)
            assert dr.offered_eps > 0
            assert dr.wall_s > 0
        finally:
            fleet.close()
