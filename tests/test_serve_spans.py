"""Spans of the served path on the profiler's clock.

A ``JetServer`` and a ``FleetServer`` (``mode="ref"``) serve a few rounds of
events under ``jax.profiler.start_trace``; the ``.xplane.pb`` it writes is
read back with ``jax.profiler.ProfileData``. Each round's events are
submitted back to back, so the round forms one batch (the collection window
is far longer than the submits take); the assertions still take the batch
sizes the server reports as the truth.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.data import JetConfig, jet_batch
from repro.kernels.cascade_mlp import cascade_mlp_ref
from repro.models import mlp as mlp_lib
from repro.serve import JetServer
from repro.serve.fleet import FleetServer, TenantSpec

ROUNDS = [4, 2, 4, 1, 2, 4]
STEP_PARTS = ["serve.to_device", "serve.dispatch", "serve.to_host",
              "serve.reply"]


@pytest.fixture(scope="module")
def model():
    jc = JetConfig(n_particles=16, n_features=8, n_classes=5, seed=0)
    params = mlp_lib.mlp_init(jax.random.key(0), 8, [16, 16, 5])
    xcal, _ = jet_batch(jc, 64, 1)
    q = mlp_lib.to_quantized(params, xcal)
    x, _ = jet_batch(jc, sum(ROUNDS), 7)
    xq = np.clip(np.round(x / 2.0 ** q.e_in), -128, 127).astype(np.int8)
    return q, xq


def _read_spans(log_dir):
    """``(line, name, start_ns, end_ns, args)`` of every ``serve.`` and
    ``fleet.`` span on the host's thread lines."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    data = ProfileData.from_file(paths[0])
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("serve.", "fleet.")):
                    out.append((k, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _serve_rounds(submit, xq):
    """Submit each round back to back and wait for its answers."""
    answers, start = [], 0
    for k in ROUNDS:
        reqs = [submit(xq[start + j]) for j in range(k)]
        answers.extend(r.wait(30) for r in reqs)
        start += k
    return np.stack(answers)


@pytest.fixture(scope="module")
def jet_trace(model, tmp_path_factory):
    """One JetServer's rounds under the profiler, the server closed (its
    worker joined, so every span has ended) before the trace stops."""
    q, xq = model
    log_dir = str(tmp_path_factory.mktemp("jet-trace"))
    srv = JetServer(q, mode="ref", max_batch=max(ROUNDS),
                    window_us=100_000.0)
    jax.profiler.start_trace(log_dir)
    try:
        answers = _serve_rounds(srv.submit, xq)
    finally:
        srv.close()
        jax.profiler.stop_trace()
    return {"spans": _read_spans(log_dir), "answers": answers,
            "batch_sizes": list(srv.stats.batch_sizes)}


@pytest.fixture(scope="module")
def fleet_trace(model, tmp_path_factory):
    """A two-replica fleet: every event through ``submit`` or ``offer``,
    then one ``infer_batch``."""
    q, xq = model
    log_dir = str(tmp_path_factory.mktemp("fleet-trace"))
    fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                    replicas=2)], policy="rr")
    jax.profiler.start_trace(log_dir)
    try:
        half = len(xq) // 2
        reqs = ([fleet.submit(x, "m") for x in xq[:half]]
                + [fleet.offer(x, "m") for x in xq[half:]])
        answers = np.stack([r.wait(30) for r in reqs])
        batch = fleet.infer_batch(list(xq[:5]), "m")
    finally:
        fleet.close()
        jax.profiler.stop_trace()
    return {"spans": _read_spans(log_dir), "answers": answers,
            "batch": batch, "replica_counts": fleet.replica_counts("m"),
            "n_submits": len(xq)}


def _named(spans, name):
    return sorted((s for s in spans if s[1] == name), key=lambda s: s[2])


def test_one_step_per_served_batch(jet_trace):
    steps = _named(jet_trace["spans"], "serve.step")
    sizes = [s[4]["size"] for s in steps]
    assert sizes == jet_trace["batch_sizes"]
    assert sum(sizes) == sum(ROUNDS)
    assert [s[4]["step"] for s in steps] == list(range(1, len(steps) + 1))


def test_reply_tags_its_events(jet_trace):
    replies = _named(jet_trace["spans"], "serve.reply")
    assert [s[4]["events"] for s in replies] == jet_trace["batch_sizes"]


def test_step_parts_nest_in_order_on_the_worker_line(jet_trace):
    spans = jet_trace["spans"]
    steps = _named(spans, "serve.step")
    assert len({s[0] for s in steps}) == 1
    for line, _, t0, t1, _ in steps:
        inside = sorted((s for s in spans if s[0] == line
                         and s[1] != "serve.step"
                         and t0 <= s[2] and s[3] <= t1),
                        key=lambda s: s[2])
        assert [s[1] for s in inside] == STEP_PARTS
        ends = [s[3] for s in inside]
        starts = [s[2] for s in inside]
        assert all(e <= s for e, s in zip(ends, starts[1:]))


def test_collect_and_wait_precede_each_step(jet_trace):
    spans = jet_trace["spans"]
    steps = _named(spans, "serve.step")
    collects = _named(spans, "serve.collect")
    assert len(collects) == len(steps)
    for c, s in zip(collects, steps):
        assert c[0] == s[0] and c[3] <= s[2]
    assert _named(spans, "serve.wait")


def test_new_shape_marks_each_sizes_first_step(jet_trace):
    steps = _named(jet_trace["spans"], "serve.step")
    seen, want = set(), []
    for s in steps:
        want.append(int(s[4]["size"] not in seen))
        seen.add(s[4]["size"])
    assert [s[4]["new_shape"] for s in steps] == want
    assert 0 in want, "no batch size was served twice"


def test_one_submit_span_per_submit(fleet_trace):
    subs = _named(fleet_trace["spans"], "fleet.submit")
    assert len(subs) == fleet_trace["n_submits"]
    per_replica = np.bincount([s[4]["replica"] for s in subs], minlength=2)
    # infer_batch scatters without FleetServer.submit: its 5 events are
    # counted per replica but carry no fleet.submit span.
    counts = np.array(fleet_trace["replica_counts"])
    assert per_replica.sum() + 5 == counts.sum()
    n = fleet_trace["n_submits"]
    assert per_replica.tolist() == [n - n // 2, n // 2]   # round robin


def test_infer_batch_span(fleet_trace):
    spans = _named(fleet_trace["spans"], "fleet.infer_batch")
    assert len(spans) == 1
    args = spans[0][4]
    assert args["events"] == 5
    counts = fleet_trace["batch"].replica_counts
    assert args["replica_counts"] == "/".join(map(str, counts))
    steps = _named(fleet_trace["spans"], "serve.step")
    assert sum(s[4]["size"] for s in steps) == fleet_trace["n_submits"] + 5


def test_answers_unchanged_without_a_profiler(model, jet_trace,
                                              fleet_trace):
    q, xq = model
    srv = JetServer(q, mode="ref", max_batch=max(ROUNDS),
                    window_us=100_000.0)
    fleet = FleetServer([TenantSpec(name="m", qmlp=q, mode="ref",
                                    replicas=2)], policy="rr")
    try:
        plain = _serve_rounds(srv.submit, xq)
        plain_fleet = np.stack([fleet.submit(x, "m").wait(30) for x in xq])
    finally:
        srv.close()
        fleet.close()
    want = np.asarray(jax.vmap(lambda x: cascade_mlp_ref(x, q))(xq))
    np.testing.assert_array_equal(plain, want)
    np.testing.assert_array_equal(plain_fleet, want)
    np.testing.assert_array_equal(jet_trace["answers"], want)
    np.testing.assert_array_equal(fleet_trace["answers"], want)
