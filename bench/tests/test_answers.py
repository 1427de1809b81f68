"""How the harness keeps, compares and counts answers of a kind's precision.

A small float kind, defined here, stands in for a configuration whose
answers are not int8; no cell and no configuration file serve it. Runs on
the CPU in a few seconds:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_answers.py
"""
from __future__ import annotations

import pathlib
import sys
import types

import ml_dtypes
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import harness  # noqa: E402

PEAKS = harness.read_json(BENCH / "peaks.json")["TPU v5 lite"]
#: The float kind's tolerance on each logit, relative to the logit's size
#: and at least 1e-3 of it: float32 accumulation over 16 terms stays far
#: inside it, while bfloat16 (8 bits of mantissa, a relative step of
#: 2**-8) does not.
TOL = 1e-3


def _float_reference(cfg, model, x):
    return (x.astype(np.float32) @ model["w"]).astype(np.float32)


def _float_control(cfg, model, x):
    """The reference in bfloat16: inputs, weights and logits rounded."""
    bf16 = lambda a: a.astype(ml_dtypes.bfloat16).astype(np.float32)
    return bf16(bf16(x.astype(np.float32)) @ bf16(model["w"]))


def _float_mismatched(got, want):
    err = np.abs(got.astype(np.float64) - want.astype(np.float64))
    return (err > TOL * np.maximum(np.abs(want), 1.0)).reshape(
        len(got), -1).any(axis=1)


FLOAT_KIND = types.SimpleNamespace(
    reference=_float_reference, mismatched=_float_mismatched,
    control=_float_control, PEAK="bf16_flops", macs_per_event=lambda cfg: 16 * 4,
    min_bytes=lambda cfg, b: b * (16 + 4) * 4)
#: A kind with no rule of its own: exact equality of every element.
INT8_KIND = types.SimpleNamespace(
    reference=lambda cfg, model, x: x[:, :4].astype(np.int8))


def _session(kind, n=8, seed=1):
    rng = np.random.default_rng(seed)
    return types.SimpleNamespace(
        kind=kind, cfg={}, model={"w": rng.normal(size=(16, 4))
                                  .astype(np.float32)},
        pool=rng.integers(-100, 100, (n, 16)).astype(np.int8))


def _record(s, answers):
    """A window in which event ``i`` asked for ``pool[i]`` and got
    ``answers[i]``."""
    want = s.kind.reference(s.cfg, s.model, s.pool)
    rec = harness.Record(len(answers), want.shape[1:], want.dtype,
                         np.random.default_rng(0), len(s.pool))
    rec.idx[:] = np.arange(len(answers))
    for i, a in enumerate(answers):
        rec.take(i, types.SimpleNamespace(
            error=None, t_submit=0.0, t_start=0.0, t_done=0.0, result=a),
            1.0)
    rec.n = len(answers)
    return rec


def _mismatched(s, answers):
    return harness.check(s, _record(s, answers))["checks"][
        "mismatched_events"]["value"]


def test_float32_answer_is_kept_at_full_precision():
    s = _session(FLOAT_KIND)
    want = s.kind.reference(s.cfg, s.model, s.pool)
    rec = _record(s, list(want))
    assert rec.answers.dtype == np.float32
    assert rec.answers[:rec.n].tobytes() == want.tobytes()
    assert not rec.uncastable.any()


def test_tolerance_passes_inside_and_fails_one_element_beyond():
    s = _session(FLOAT_KIND)
    want = s.kind.reference(s.cfg, s.model, s.pool)
    scale = np.maximum(np.abs(want), 1.0)
    inside = want + (0.5 * TOL * scale).astype(np.float32)
    assert _mismatched(s, list(inside)) == 0
    beyond = inside.copy()
    beyond[3, 2] = want[3, 2] + 2 * TOL * scale[3, 2]
    assert _mismatched(s, list(beyond)) == 1


def test_float_control_fails_the_tolerance():
    s = _session(FLOAT_KIND, n=64)
    want = s.kind.reference(s.cfg, s.model, s.pool)
    rec = _record(s, list(want))
    assert harness.check(s, rec, control.control_answers)["checks"][
        "mismatched_events"]["value"] > 0


@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
def test_nonfinite_fails_whatever_the_kind_says(bad):
    s = _session(types.SimpleNamespace(
        reference=_float_reference,
        mismatched=lambda got, want: np.zeros(len(got), bool)))
    answers = s.kind.reference(s.cfg, s.model, s.pool)
    answers[5, 1] = bad
    result = harness.check(s, _record(s, list(answers)))
    assert result["checks"]["mismatched_events"]["value"] == 1
    assert result["answers_spread"]["nonfinite"] == 1 / answers.size


@pytest.mark.parametrize("kind,dtype", [
    (FLOAT_KIND, np.float64),   # more precision than the reference holds
    (INT8_KIND, np.float32),    # a float answer truncated into int8
    (INT8_KIND, np.int16)])     # a wider integer
def test_answer_the_reference_cannot_hold_safely_is_a_mismatch(kind, dtype):
    s = _session(kind)
    want = s.kind.reference(s.cfg, s.model, s.pool)
    answers = list(want)
    answers[2] = want[2].astype(dtype)  # equal in value, not in dtype
    rec = _record(s, answers)
    assert rec.uncastable.tolist() == [i == 2 for i in range(len(want))]
    assert harness.check(s, rec)["checks"]["mismatched_events"][
        "value"] == 1


def test_control_of_another_dtype_is_a_mismatch():
    s = _session(INT8_KIND)
    want = s.kind.reference(s.cfg, s.model, s.pool)
    rec = _record(s, list(want))
    assert harness.check(s, rec)["checks"]["mismatched_events"][
        "value"] == 0
    floats = lambda kind, cfg, model, pool: kind.reference(
        cfg, model, pool).astype(np.float32)
    assert harness.check(s, rec, floats)["checks"]["mismatched_events"][
        "value"] == len(want)


def test_kind_rule_of_the_wrong_shape_is_refused():
    s = _session(types.SimpleNamespace(
        reference=_float_reference, mismatched=lambda got, want: False))
    want = s.kind.reference(s.cfg, s.model, s.pool)
    with pytest.raises(ValueError, match="shape"):
        harness.check(s, _record(s, list(want)))


def test_answers_spread_follows_the_dtype():
    s = _session(INT8_KIND)
    want = s.kind.reference(s.cfg, s.model, s.pool)
    ints = want.copy()
    ints[0] = [0, 127, -128, 5]
    spread = harness.check(s, _record(s, list(ints)))["answers_spread"]
    assert spread == {"zero": float(np.mean(ints == 0)),
                      "clipped": float(np.mean((ints == 127)
                                               | (ints == -128)))}
    s = _session(FLOAT_KIND)
    want = s.kind.reference(s.cfg, s.model, s.pool)
    floats = want.copy()
    floats[1, 0] = 0.0
    floats[4, 3] = np.nan
    spread = harness.check(s, _record(s, list(floats)))["answers_spread"]
    assert spread["zero"] == 1 / want.size
    assert spread["nonfinite"] == 1 / want.size
    assert spread["max_abs_diff"] == float(abs(np.float64(want[1, 0])))


def _window(n=8):
    """A fixed window: eight answered events, five of them inside it, and
    four traced kernel calls."""
    got = np.linspace(1.0, 2.5, n)
    rec = types.SimpleNamespace(
        n=n, got=got, due=got - 1e-3, offer_us=np.full(n, 30.0),
        t_submit=got - 2e-3, t_start=got - 1.5e-3, t_done=got - 1e-4)
    trace = {"op_calls_us": {"custom-call.1": [37.25, 36.5, 38.0, 12.0],
                             "fusion.2": [1.0, 2.0]}}
    return {"rec": rec, "t0": 0.5, "t_close": 2.0, "trace": trace,
            "batch_sizes": [64, 64, 63, 17]}


def _ctx(kind, cfg=None):
    s = types.SimpleNamespace(workload="w", kind=kind, cfg=cfg or {},
                              setup_s=1.0, peaks=PEAKS)
    return harness.timings(s, _window())


def test_kind_names_its_peak():
    assert _ctx(FLOAT_KIND).peak_ops == PEAKS["bf16_flops"]
    int8 = harness.resolve("deepsets-32.drain")
    assert _ctx(int8["kind"], int8["config"]).peak_ops == PEAKS["int8_ops"]


#: The readers' values on ``_window()`` with each divided by
#: ``peaks["int8_ops"]`` directly, as they read before a kind named its peak.
INT8_READINGS = {
    ("deepsets-32.drain", "kernel_roofline.drain"): 0.1581825581825582,
    ("deepsets-32.drain", "mfu.drain"): 1.5166751484308738e-07,
    ("jsc-m.sparse", "kernel_roofline.drain"): 0.2992681392681393,
    ("jsc-m.sparse", "mfu.drain"): 5.732315521628499e-07,
}


@pytest.mark.parametrize("cell,metric", sorted(INT8_READINGS))
def test_int8_readers_read_as_before(cell, metric):
    c = harness.resolve(cell)
    reader = harness.load_module(BENCH / "metrics" / f"{metric}.py")
    assert reader.read(_ctx(c["kind"], c["config"])) == INT8_READINGS[
        (cell, metric)]
