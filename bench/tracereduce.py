"""Reduce a profiler trace of one measured window to device metrics.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps two
lists, in nanoseconds on the profiler's clock:

  * ``device``: ``[name, start, duration]`` of every operation on the chip's
    "XLA Ops" line;
  * ``spans``: ``[thread, name, start, duration]`` of the benchmark's own host
    spans (names starting ``bench.``), which ``jax.profiler.TraceAnnotation``
    put on the same clock.

``reduce`` works on those lists alone, so a small recorded copy of them
checks it (``bench/tests/test_tracereduce.py``). Busy time is the union of
the device operations' intervals inside the ``bench.window`` span; idle is
the rest of that span. Each idle gap is named by the benchmark spans open at
its midpoint, on every host thread.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional

import numpy as np

WINDOW_SPAN = "bench.window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: A device operation's name on the TPU is its HLO instruction's text,
#: ``%name = <shape> <opcode>(<operands>), ...``; the opcode is the first
#: word after `` = `` that an opening parenthesis follows.
HLO_TEXT = re.compile(r"^(\S+) = (.*?) ([a-z][a-z0-9\-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of a device operation, or its whole name when the
    name is not an HLO instruction's text."""
    m = HLO_TEXT.match(name)
    return m.group(3) if m else name


def label(name: str) -> str:
    """A short name for the breakdown: ``%name opcode shape``."""
    m = HLO_TEXT.match(name)
    if not m:
        return name
    shape = m.group(2)
    shape = "" if shape.startswith("(") else " " + shape.split("{")[0]
    return f"{m.group(1)} {m.group(3)}{shape}"


def load(log_dir: str) -> dict:
    """The device operations and benchmark spans of the trace in ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    device: List[list] = []
    spans: List[list] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.extend([e.name, e.start_ns, e.duration_ns]
                                  for e in line.events)
        elif plane.name.startswith("/host:"):
            # Every Python thread's line is called "python": number them.
            for k, line in enumerate(plane.lines):
                spans.extend([f"{line.name}#{k}", e.name, e.start_ns,
                              e.duration_ns]
                             for e in line.events
                             if e.name.startswith("bench."))
    return {"device": device, "spans": spans}


def _union(starts: np.ndarray, ends: np.ndarray):
    """Disjoint, sorted intervals covering the given ones."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(reach[idx[1:] - 1], reach[-1])


def reduce(trace: dict, *, top: int = 10) -> Optional[dict]:
    """Busy and window seconds, each operation's call durations, and the
    breakdown. None when the trace has no window span or no device
    operation inside it."""
    windows = [s for s in trace["spans"] if s[1] == WINDOW_SPAN]
    if not windows or not trace["device"]:
        return None
    w0 = float(windows[0][2])
    w1 = w0 + float(windows[0][3])
    names = [d[0] for d in trace["device"]]
    st = np.array([d[1] for d in trace["device"]], np.float64)
    en = st + np.array([d[2] for d in trace["device"]], np.float64)
    inside = (en > w0) & (st < w1)
    if not inside.any():
        return None
    cs, ce = np.clip(st, w0, w1), np.clip(en, w0, w1)
    us, ue = _union(cs[inside], ce[inside])
    busy_ns = float((ue - us).sum())

    per_op: Dict[str, float] = collections.defaultdict(float)
    calls: Dict[str, List[float]] = collections.defaultdict(list)
    for i in np.flatnonzero(inside):
        per_op[label(names[i])] += ce[i] - cs[i]
        calls[names[i]].append((en[i] - st[i]) * 1e-3)

    gap_s = np.concatenate([[w0], ue])
    gap_e = np.concatenate([us, [w1]])
    keep = gap_e > gap_s
    gap_s, gap_e = gap_s[keep], gap_e[keep]
    labels = _labels(trace["spans"], (gap_s + gap_e) / 2)
    idle: Dict[str, float] = collections.defaultdict(float)
    for lab, g in zip(labels, gap_e - gap_s):
        idle[lab] += g

    def top_list(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_ns * 1e-9, "window_s": (w1 - w0) * 1e-9,
            "op_calls_us": dict(calls),
            "breakdown": {"device_ops": top_list(per_op),
                          "idle_gaps": top_list(idle)}}


def _labels(spans: list, at: np.ndarray) -> List[str]:
    """For each time in ``at``: the benchmark spans open then, joined."""
    by_line: Dict[str, list] = collections.defaultdict(list)
    for line, name, s, d in spans:
        if name != WINDOW_SPAN:
            by_line[line].append((float(s), float(s) + float(d), name))
    open_at: List[List[str]] = [[] for _ in range(at.size)]
    for rows in by_line.values():
        rows.sort()
        s = np.array([r[0] for r in rows])
        e = np.array([r[1] for r in rows])
        # Spans of one thread do not nest: the last to start before t is the
        # only one that can be open at t.
        j = np.searchsorted(s, at, side="right") - 1
        for k in np.flatnonzero((j >= 0) & (e[np.maximum(j, 0)] > at)):
            open_at[k].append(rows[j[k]][2])
    return ["+".join(sorted(o)) if o else "no benchmark span"
            for o in open_at]
