"""The correctness check passes sound runs and fails the control and faults.

Runs on the CPU (Pallas in interpret mode) at a small size: four batch
sizes, a pool of 64 events and half-second windows. Not part of tier-1
(``testpaths = tests``); run by hand:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

CELLS = [w["name"] for w in
         harness.read_json(harness.ROOT / "BENCHMARK.json")["workloads"]]
SMALL = dict(require_tpu=False, max_batch=4, pool_size=64,
             log=lambda _s: None)
SEED = 2 ** 33 + 5


def _run(workload, **kw):
    return harness.run(workload, SEED, 0.5, False, **SMALL, **kw)["result"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    result = _run(workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_int4_control_is_not_correct(workload):
    sys.path.insert(0, str(BENCH))
    import control
    result = _run(workload, answers_from=control.int4_answers)
    assert not result["correct"]
    assert result["checks"]["mismatched_events"]["value"] > 0


def _altered(f):
    """The kernel's answer with one value changed where it is produced."""
    def g(x, *a, **k):
        out = f(x, *a, **k)
        return out.at[0, 0].add(1)
    return g


def _half_set(f):
    """Half of the event's rows left out before the kernel runs."""
    def g(x, *a, **k):
        return f(x.at[x.shape[0] // 2:].set(0), *a, **k)
    return g


@pytest.mark.parametrize("fault", [_altered, _half_set])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    import repro.serve as serve
    kernel = ("deepsets" if harness.resolve(workload)["config"]["kind"]
              == "deepsets" else "cascade_mlp")
    monkeypatch.setattr(serve, kernel, fault(getattr(serve, kernel)))
    result = _run(workload)
    assert not result["correct"]
    assert result["checks"]["mismatched_events"]["value"] > 0
