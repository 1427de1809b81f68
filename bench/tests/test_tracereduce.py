"""The trace reduction on a hand-made trace with known answers.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import tracereduce  # noqa: E402

# Window [1000, 11000) ns. Device: two overlapping ops, one kernel call, one
# op that straddles the window's start and one after its end.
TRACE = {
    "device": [["straddle", 500, 1000], ["a", 2000, 1000], ["b", 2500, 1500],
               ["kernel", 6000, 1000], ["after", 12000, 1000]],
    "spans": [["main", "bench.window", 1000, 10000],
              ["gen", "bench.wait_arrival", 4500, 1000],
              ["gen", "bench.offer", 8500, 1000],
              ["col", "bench.collect", 0, 20000]],
}


def test_busy_window_and_idle_gaps():
    r = tracereduce.reduce(TRACE)
    # busy: [1000,1500) + [2000,4000) + [6000,7000)
    assert r["busy_s"] == pytest.approx(3500e-9)
    assert r["window_s"] == pytest.approx(10000e-9)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({
        "bench.collect+bench.offer": 4000e-9,
        "bench.collect+bench.wait_arrival": 2000e-9,
        "bench.collect": 500e-9})
    assert [k for k, _ in r["breakdown"]["idle_gaps"]] == [
        "bench.collect+bench.offer", "bench.collect+bench.wait_arrival",
        "bench.collect"]
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_device_ops_are_clipped_to_the_window():
    r = tracereduce.reduce(TRACE)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"b": 1500e-9, "a": 1000e-9,
                                 "kernel": 1000e-9, "straddle": 500e-9})
    assert "after" not in r["op_calls_us"]
    assert r["op_calls_us"]["kernel"] == pytest.approx([1.0])
    assert r["op_calls_us"]["straddle"] == pytest.approx([1.0])


def test_nothing_to_read():
    assert tracereduce.reduce({"device": TRACE["device"], "spans": []}) is None
    assert tracereduce.reduce({"device": [], "spans": TRACE["spans"]}) is None
    assert tracereduce.reduce({"device": [["after", 12000, 10]],
                               "spans": TRACE["spans"]}) is None


# Device operations as a TPU trace names them: the HLO instruction's text.
TPU_KERNEL = ('%vmap__.1 = s8[64,128]{1,0:T(8,128)(4,1)S(1)} custom-call('
              's8[64,128]{1,0:T(8,128)(4,1)S(1)} %pad_bitcast_fusion), '
              'custom_call_target="tpu_custom_call", '
              'frontend_attributes={kernel_metadata={}}')
TPU_COPY = ('%copy.1 = s8[1,64,5]{1,2,0:T(8,128)(4,1)} '
            'copy(s8[1,64,5]{2,1,0:T(8,128)(4,1)S(1)} %custom-call.3)')
TPU_TUPLE = ('%copy-start = (s8[128,128]{1,0}, u32[]{:S(2)}) '
             'copy-start(s8[128,128]{1,0:T(8,128)(4,1)} %constant.24)')


@pytest.mark.parametrize("name, opcode, label", [
    (TPU_KERNEL, "custom-call", "%vmap__.1 custom-call s8[64,128]"),
    (TPU_COPY, "copy", "%copy.1 copy s8[1,64,5]"),
    (TPU_TUPLE, "copy-start", "%copy-start copy-start"),
    ("custom-call.2", "custom-call.2", "custom-call.2"),
])
def test_opcode_and_label_of_hlo_text(name, opcode, label):
    assert tracereduce.opcode(name) == opcode
    assert tracereduce.label(name) == label


@pytest.mark.parametrize("device, want", [
    ([[TPU_KERNEL, 2000, 1000], [TPU_COPY, 3000, 500],
      [TPU_TUPLE, 4000, 100]], [1.0]),
    ([["custom-call.2", 2000, 2000], ["copy.1", 5000, 500]], [2.0]),
    ([["kernel", 2000, 3000], ["copy.1", 5000, 500]], []),
    ([["copy.1", 5000, 500]], []),
])
def test_kernel_calls_by_name_or_custom_call(device, want):
    r = tracereduce.reduce({"device": device, "spans": TRACE["spans"]})
    assert harness.kernel_calls_us(r) == pytest.approx(want)
