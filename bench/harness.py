"""One run of one benchmark cell, driven by the files that name it.

``BENCHMARK.json`` names each cell's configuration and traffic mix. The
harness finds everything else by those names:

  * ``bench/configs/<config>.json``: the sizes, and the model ``kind``;
  * ``bench/kinds/<kind>.py``: builds the weights in the configuration's
    precision and the program's tenant from the configuration's
    ``weights_seed``, makes the inputs from ``--seed``, computes the plain
    reference and the control (``control``: the answers one precision
    below the configuration's), counts the operations and bytes of a served
    call, and names the ``repro.serve`` function that serves it
    (``FAULT_SITE``, which the fault tests wrap). Optional: ``PEAK``, the
    key of ``bench/peaks.json`` its operations are counted against
    (``"int8_ops"`` by default), and ``mismatched(got, want)``, one flag
    per event (exact equality of every element by default; see ``check``);
  * ``bench/traffic/<traffic>.json``: the mix's parameters, among them the
    ``generator``;
  * ``bench/generators/<generator>.py``: an open-loop ``schedule`` of arrival
    offsets, or a closed loop's number of ``outstanding`` events;
  * ``bench/metrics/<metric>.py``: one reader per metric, ``read(ctx)``,
    which returns a number or None when it finds nothing to read;
  * ``bench/peaks.json``: the chip's peaks, keyed by JAX's ``device_kind``.

A run builds one ``FleetServer`` with one replica at the program's defaults,
warms every batch size its window can form through the public ``submit``,
then drives the traffic for the window. Open-loop latency runs from each
event's intended arrival to the moment the benchmark's collector holds the
answer. After the window every answer, kept in the reference's dtype, is
compared with the reference.
"""
from __future__ import annotations

import collections
import contextlib
import importlib.util
import inspect
import json
import pathlib
import queue
import shutil
import sys
import tempfile
import threading
import time
import types
from typing import Callable, Optional

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: JAX's persistent compile cache: a fixed path inside the checkout, so that
#: only a cell's first run there compiles.
CACHE_DIR = BENCH / ".jax_cache"
#: Per-run details that are too long for the result line.
OUT_DIR = BENCH / ".out"
#: Distinct events each run draws its traffic from.
POOL = 8192
#: How long after the window closes an answer may still come.
GRACE_S = 60.0
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: pathlib.Path) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, spec: Optional[dict] = None) -> dict:
    """The cell's entry, configuration, traffic and modules, by name."""
    spec = spec or read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg = read_json(ROOT / cfg_entry["file"])
    traffic = read_json(BENCH / "traffic" / f"{w['traffic']}.json")
    return {"spec": spec, "workload": w, "config": cfg, "traffic": traffic,
            "kind": load_module(BENCH / "kinds" / f"{cfg['kind']}.py"),
            "generator": load_module(
                BENCH / "generators" / f"{traffic['generator']}.py")}


def metrics_of(spec: dict, workload: str) -> tuple:
    """(end-to-end, per-layer) metric entries this cell reports."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return e2e, per_layer


class CompileClock:
    """JAX's backend compile seconds and persistent-cache hits while open
    (JAX times a cache read as a compile). Copied from ``chip_smoke.py``."""

    def __enter__(self) -> "CompileClock":
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += seconds
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def enable_compile_cache() -> None:
    """Always ``CACHE_DIR``, even where ``JAX_COMPILATION_CACHE_DIR`` is set:
    a directory named from outside the checkout could be shared by two
    checkouts of different code. The thresholds drop to zero because the
    served kernels compile in well under JAX's default minimum."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def percentile(a, q: float) -> Optional[float]:
    a = np.asarray(a, np.float64)
    return float(np.percentile(a, q)) if a.size else None


def castable(frm: np.dtype, to: np.dtype) -> bool:
    """Whether ``to`` holds every value of ``frm``. ``Record.take`` asks
    once per event on the collector's thread, so equal dtypes are answered
    first: ``np.can_cast`` costs about 20 times the comparison."""
    return frm == to or np.can_cast(frm, to, "safe")


def floating(dtype: np.dtype) -> bool:
    """Answers that are neither integers nor booleans (ml_dtypes' bfloat16
    has the kind ``V``, so numpy's ``floating`` would miss it)."""
    return np.dtype(dtype).kind not in "biu"


def warm(fleet, name: str, pool: np.ndarray, max_batch: int,
         log: Callable[[str], None]) -> dict:
    """Serve every batch size 1..max_batch through ``FleetServer.submit``.

    ``k`` events submitted back to back reach the worker before it wakes
    (the submitting thread keeps the interpreter lock for far longer than
    ``k`` submits take), so its window forms one batch of ``k``. The batch
    sizes the server reports say which sizes formed; missing ones are tried
    again. A second pass over every size must compile nothing.
    """
    with CompileClock() as first:
        for attempt in range(8):
            seen = set(fleet.stats(name).batch_sizes)
            need = [k for k in range(max_batch, 0, -1) if k not in seen]
            if not need:
                break
            for k in need:
                reqs = [fleet.submit(pool[j % len(pool)], name)
                        for j in range(k)]
                for r in reqs:
                    r.wait(timeout=600)
    missing = sorted(set(range(1, max_batch + 1))
                     - set(fleet.stats(name).batch_sizes))
    if missing:
        raise RuntimeError(f"warm-up never formed batch sizes {missing}")
    with CompileClock() as second:
        for k in range(max_batch, 0, -1):
            for r in [fleet.submit(pool[j], name) for j in range(k)]:
                r.wait(timeout=600)
    log(f"warm-up: batch sizes 1..{max_batch} in {attempt + 1} pass(es), "
        f"{first.compiles} compiles ({first.cache_hits} cache hits, "
        f"{first.seconds:.3f} s); second pass {second.compiles} compiles")
    return {"compiles": first.compiles, "cache_hits": first.cache_hits,
            "compile_s": first.seconds, "second_pass_compiles":
                second.compiles}


class Record:
    """What the window produced, per event (arrays indexed by event).

    Each event's input is ``pool[idx[i]]``, drawn from the seed. A closed
    loop does not know its count ahead, so the arrays double when full.
    Answers are kept in ``dtype``, the reference's; an answer that dtype
    cannot hold safely is not kept but flagged in ``uncastable``.
    """

    FIELDS = ("due", "offer_us", "lag_us", "got", "t_submit", "t_start",
              "t_done")

    def __init__(self, n: int, out_shape: tuple, dtype, rng,
                 pool_size: int):
        self.n, self.cap = 0, 0
        self.rng, self.pool_size = rng, pool_size
        self.out_shape, self.dtype = tuple(out_shape), np.dtype(dtype)
        self.error: Optional[BaseException] = None
        for f in self.FIELDS:
            setattr(self, f, np.empty(0))
        self.idx = np.empty(0, np.int64)
        self.answers = np.empty((0,) + self.out_shape, self.dtype)
        self.uncastable = np.empty(0, bool)
        self.ensure(n)

    def ensure(self, n: int) -> None:
        if n <= self.cap:
            return
        add = max(n, 2 * self.cap) - self.cap
        for f in self.FIELDS:
            setattr(self, f, np.concatenate([getattr(self, f),
                                             np.full(add, np.nan)]))
        self.idx = np.concatenate(
            [self.idx, self.rng.integers(0, self.pool_size, add)])
        self.answers = np.concatenate(
            [self.answers, np.zeros((add,) + self.out_shape, self.dtype)])
        self.uncastable = np.concatenate(
            [self.uncastable, np.zeros(add, bool)])
        self.cap += add

    def take(self, i: int, req, t_got: float) -> None:
        """Keep request ``i``'s answer and the program's timestamps."""
        if req.error is not None:
            self.error = req.error
            return
        self.got[i] = t_got
        self.t_submit[i] = req.t_submit
        self.t_start[i] = req.t_start
        self.t_done[i] = req.t_done
        result = np.asarray(req.result)
        # Assignment would cast unsafely without a word (a float logit into
        # int8 truncates), so such an answer is a mismatch, never kept.
        if castable(result.dtype, self.dtype):
            self.answers[i] = result
        else:
            self.uncastable[i] = True


def _wait(req, deadline: list) -> bool:
    """Wait for ``req`` until ``deadline[0]`` (moved once the window closes)."""
    while not req.event.wait(timeout=0.25):
        if time.perf_counter() > deadline[0]:
            return False
    return True


def drive_open(fleet, name, pool, rec, offsets, span, seconds):
    """Offer each event at its intended arrival; a collector thread waits for
    the answers in order and takes the time it holds each."""
    deadline = [float("inf")]
    inbox: "queue.SimpleQueue" = queue.SimpleQueue()

    def collect():
        while True:
            item = inbox.get()
            if item is None:
                return
            i, req = item
            if req is None:
                continue
            with span("bench.collect"):
                ok = _wait(req, deadline)
            if ok:
                rec.take(i, req, time.perf_counter())

    collector = threading.Thread(target=collect, name="bench-collector")
    collector.start()
    idx = rec.idx.tolist()
    t0 = time.perf_counter()
    due = (t0 + offsets).tolist()
    try:
        with span("bench.window"):
            for i, t_due in enumerate(due):
                wait = t_due - time.perf_counter()
                if wait > 0:
                    with span("bench.wait_arrival"):
                        time.sleep(wait)
                a = time.perf_counter()
                with span("bench.offer"):
                    req = fleet.offer(pool[idx[i]], name)
                rec.offer_us[i] = (time.perf_counter() - a) * 1e6
                rec.lag_us[i] = (a - t_due) * 1e6
                inbox.put((i, req))
            rest = t0 + seconds - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
        t_close = time.perf_counter()
    finally:
        deadline[0] = time.perf_counter() + GRACE_S
        inbox.put(None)
        collector.join()
    rec.n = len(due)
    rec.due[:rec.n] = due
    return t0, t_close


def drive_closed(fleet, name, pool, rec, outstanding, span, seconds):
    """Keep ``outstanding`` events in flight; each answer lets the next in."""
    deadline = [float("inf")]
    window = span("bench.window")
    window.__enter__()
    open_window = True
    inflight = collections.deque()
    n = 0

    def submit():
        nonlocal n
        rec.ensure(n + 1)
        with span("bench.submit"):
            a = time.perf_counter()
            inflight.append((n, fleet.submit(pool[rec.idx[n]], name)))
            rec.due[n] = a
        n += 1

    t0 = time.perf_counter()
    t_end = t0 + seconds
    try:
        for _ in range(outstanding):
            submit()
        while inflight:
            i, req = inflight.popleft()
            with span("bench.collect"):
                ok = _wait(req, deadline)
            t = time.perf_counter()
            if ok:
                rec.take(i, req, t)
            if t < t_end:
                submit()
            elif open_window:
                window.__exit__(None, None, None)
                open_window = False
                deadline[0] = t + GRACE_S
    finally:
        if open_window:
            window.__exit__(None, None, None)
    rec.n = n
    return t0, t_end


@contextlib.contextmanager
def profiled(enabled: bool):
    """The profiler on for the block, then its trace reduced (or None)."""
    box = {"trace": None}
    if not enabled:
        yield box
        return
    import jax
    import tracereduce
    log_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield box
    finally:
        jax.profiler.stop_trace()
        t = time.perf_counter()
        try:
            box["raw"] = tracereduce.load(log_dir)
            box["trace"] = tracereduce.reduce(box["raw"])
            box["reduce_s"] = time.perf_counter() - t
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)


class Session:
    """A cell's server, built from the seed and warmed; a context manager.

    ``require_tpu=False``, ``max_batch`` and ``pool_size`` let the tests
    drive it on the CPU at a small size; the benchmark's runs never set them.
    """

    def __init__(self, workload: str, seed: int, *,
                 t_start: Optional[float] = None, require_tpu: bool = True,
                 max_batch: Optional[int] = None, pool_size: int = POOL,
                 log: Callable[[str], None] = lambda s: print(
                     s, file=sys.stderr)):
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.workload, self.seed, self.log = workload, seed, log
        self.cell = resolve(workload)
        self.cfg, self.kind = self.cell["config"], self.cell["kind"]
        self.require_tpu, self.max_batch = require_tpu, max_batch
        self.pool_size = pool_size

    def __enter__(self) -> "Session":
        import jax
        chips = self.cell["workload"]["chips"]
        devices = jax.devices()
        platform = devices[0].platform
        if self.require_tpu and platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found platform {platform!r}")
        if len(devices) < chips:
            raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
        t_devices = time.perf_counter()
        self.devices = devices[:chips]
        self.device = {"platform": platform, "kind": devices[0].device_kind,
                       "count": len(devices)}
        self.log(f"device: {self.device}")
        peaks = read_json(BENCH / "peaks.json")
        if self.require_tpu and self.device["kind"] not in peaks:
            raise KeyError(f"no peaks for device kind "
                           f"{self.device['kind']!r} in bench/peaks.json")
        self.peaks = peaks.get(self.device["kind"])
        if self.require_tpu:
            enable_compile_cache()
        if str(ROOT / "src") not in sys.path:
            sys.path.insert(0, str(ROOT / "src"))
        from repro.serve.fleet import FleetServer

        cfg, kind = self.cfg, self.kind
        # The weights come from the configuration's fixed key, not from
        # --seed: the program compiles them into every served program as
        # constants, so new weights would miss the compile cache.
        self.model = kind.build(cfg, cfg["weights_seed"])
        self.pool = kind.inputs(cfg, self.model, self.pool_size,
                                seed=[self.seed, 3])
        ref = kind.reference(cfg, self.model, self.pool[:1])
        self.out_shape, self.out_dtype = tuple(ref.shape[1:]), ref.dtype
        self.name = cfg["name"]
        opts = {} if self.max_batch is None else {"max_batch": self.max_batch}
        self.fleet = FleetServer([kind.tenant(cfg, self.model, self.name)],
                                 **opts)
        t_built = time.perf_counter()
        try:
            mb = self.max_batch or inspect.signature(
                FleetServer).parameters["max_batch"].default
            self.warmed = warm(self.fleet, self.name, self.pool, mb,
                               self.log)
        except BaseException:
            self.fleet.close()
            raise
        t_warm = time.perf_counter()
        self.setup_s = t_warm - self.t_start
        self.setup_split = {"start_to_chip_s": t_devices - self.t_start,
                            "weights_inputs_server_s": t_built - t_devices,
                            "warm_s": t_warm - t_built}
        return self

    def __exit__(self, *exc) -> None:
        self.fleet.close()

    def window(self, traffic: dict, seconds: float, trace: bool,
               rng: np.random.Generator) -> dict:
        """Drive ``traffic`` for ``seconds``, then wait for every answer."""
        import jax
        gen = load_module(BENCH / "generators" / f"{traffic['generator']}.py")
        span = (jax.profiler.TraceAnnotation if trace
                else (lambda _name: contextlib.nullcontext()))
        offsets = gen.schedule(traffic, seconds, rng) if gen.OPEN_LOOP else []
        rec = Record(len(offsets) or 1 << 16, self.out_shape,
                     self.out_dtype, rng, self.pool_size)
        before = len(self.fleet.stats(self.name).batch_sizes)
        with profiled(trace) as prof, CompileClock() as clock:
            if gen.OPEN_LOOP:
                t0, t_close = drive_open(self.fleet, self.name, self.pool,
                                         rec, offsets, span, seconds)
            else:
                t0, t_close = drive_closed(
                    self.fleet, self.name, self.pool, rec,
                    gen.outstanding(traffic), span, seconds)
        if rec.error is not None:
            self.log(f"the server raised: {rec.error!r}")
        return {"rec": rec, "t0": t0, "t_close": t_close, "clock": clock,
                "open_loop": gen.OPEN_LOOP, "trace": prof["trace"],
                "raw_trace": prof.get("raw"),
                "trace_reduce_s": prof.get("reduce_s"),
                "batch_sizes": list(
                    self.fleet.stats(self.name).batch_sizes[before:])}

    def memory_peak(self) -> int:
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in self.devices))


def timings(s: Session, win: dict) -> types.SimpleNamespace:
    """What the metric readers read, from one window."""
    rec, n = win["rec"], win["rec"].n
    answered = ~np.isnan(rec.got[:n])
    in_window = answered & (rec.got[:n] <= win["t_close"])
    batches = dict(zip(rec.t_start[:n][answered], rec.t_done[:n][answered]))
    kind, cfg = s.kind, s.cfg
    return types.SimpleNamespace(
        workload=s.workload, config=cfg, kind=kind, setup_s=s.setup_s,
        window_s=win["t_close"] - win["t0"], attempted=n,
        answered=int(answered.sum()),
        answered_in_window=int(in_window.sum()),
        latency_us=(rec.got[:n][answered] - rec.due[:n][answered]) * 1e6,
        offer_us=rec.offer_us[:n][~np.isnan(rec.offer_us[:n])],
        queue_wait_us=(rec.t_start[:n][answered]
                       - rec.t_submit[:n][answered]) * 1e6,
        service_us=np.array([(d - t) * 1e6 for t, d in batches.items()]),
        batch_sizes=win["batch_sizes"],
        ops_per_event=2 * kind.macs_per_event(cfg),
        min_bytes=lambda b: kind.min_bytes(cfg, b),
        peaks=s.peaks, trace=win["trace"],
        peak_ops=(s.peaks[getattr(kind, "PEAK", "int8_ops")]
                  if s.peaks else None))


def check(s: Session, rec: Record, answers_from: Optional[Callable] = None
          ) -> dict:
    """Every answer of the window against the plain reference, in the
    kind's precision.

    An event is mismatched where the kind's ``mismatched(got, want)`` flags
    it (without one, where any element differs from the reference), where
    its answer has a dtype the reference's cannot hold safely, or where any
    element of a floating answer is not finite, whatever the kind says.
    A float kind states in ``mismatched`` a tolerance for each quantity,
    each written with its reason and tight enough that the kind's
    ``control`` fails it.

    ``answers_from(kind, cfg, model, pool)`` puts other answers in the
    program's place (the control, and the tests' faults).
    """
    n = rec.n
    answered = ~np.isnan(rec.got[:n])
    idx = rec.idx[:n][answered]
    want = s.kind.reference(s.cfg, s.model, s.pool)[idx]
    served = rec.answers[:n][answered]
    uncastable = rec.uncastable[:n][answered]
    if answers_from is None:
        got, bad = served, uncastable
    else:
        got = np.asarray(answers_from(s.kind, s.cfg, s.model, s.pool))[idx]
        bad = np.full(len(got), not castable(got.dtype, want.dtype))
    bad = bad | mismatched(s.kind, got, want)
    return {"checks": {
                "mismatched_events": {"value": int(bad.sum()), "limit": 0},
                "unanswered_events": {"value": int(n - answered.sum()),
                                      "limit": 0}},
            "answers_spread": answers_spread(served[~uncastable],
                                             want[~uncastable])}


def mismatched(kind, got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """One flag per event: the kind's rule, or exact equality of every
    element; and any non-finite element of a floating answer."""
    flat = lambda a: a.reshape(len(a), -1)
    rule = getattr(kind, "mismatched", None)
    if rule is None:
        bad = (flat(got) != flat(want)).any(axis=1)
    else:
        bad = np.asarray(rule(got, want), bool)
        if bad.shape != (len(got),):
            raise ValueError(f"the kind's mismatched gave shape "
                             f"{bad.shape} for {len(got)} events")
    if floating(got.dtype):
        bad = bad | ~np.isfinite(flat(got)).all(axis=1)
    return bad


def answers_spread(served: np.ndarray, want: np.ndarray) -> dict:
    """How the served answers spread: the share at zero, and for integers
    the share at the dtype's ends, for floats the share not finite and the
    largest finite |served - want|."""
    if not served.size:
        return ({"zero": None, "nonfinite": None, "max_abs_diff": None}
                if floating(served.dtype) else
                {"zero": None, "clipped": None})
    zero = float(np.mean(served == 0))
    if not floating(served.dtype):
        info = np.iinfo(served.dtype)
        return {"zero": zero, "clipped": float(np.mean(
            (served == info.max) | (served == info.min)))}
    finite = np.isfinite(served)
    diff = np.abs(served.astype(np.float64) - want.astype(np.float64))
    return {"zero": zero, "nonfinite": float(np.mean(~finite)),
            "max_abs_diff": float(diff[finite].max()) if finite.any()
            else None}


def window_details(win: dict) -> dict:
    """The generator's lag and the backlog's trend, for earlier lines."""
    rec, n = win["rec"], win["rec"].n
    bs = win["batch_sizes"]
    d = {"compiles_in_window": win["clock"].compiles,
         "compile_s_in_window": win["clock"].seconds,
         "batches": len(bs),
         "mean_batch": float(np.mean(bs)) if bs else None,
         "trace_reduce_s": win.get("trace_reduce_s"),
         "error": repr(rec.error) if rec.error is not None else None}
    if win["open_loop"] and n:
        lag = rec.lag_us[:n]
        d["generator_lag_us"] = {"p50": percentile(lag, 50),
                                 "p90": percentile(lag, 90),
                                 "p99": percentile(lag, 99),
                                 "max": float(lag.max())}
        # A backlog that grows through the window shows as queue waits that
        # rise from the first quarter of the arrivals to the last.
        q = max(n // 4, 1)
        wait = (rec.t_start[:n] - rec.t_submit[:n]) * 1e6
        d["queue_wait_us_p50_first_last_quarter"] = [
            percentile(wait[:q][~np.isnan(wait[:q])], 50),
            percentile(wait[-q:][~np.isnan(wait[-q:])], 50)]
    return d


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        answers_from: Optional[Callable] = None, **session) -> dict:
    """One run; returns the result line's object and the run's details."""
    s = Session(workload, seed, **session)
    with s:
        win = s.window(s.cell["traffic"], seconds, trace,
                       np.random.default_rng([seed, 4]))
        memory_peak = s.memory_peak()
    # The reference runs after the window has closed and the server is gone.
    checked = check(s, win["rec"], answers_from)
    ctx = timings(s, win)
    e2e, per_layer = metrics_of(s.cell["spec"], workload)
    metrics = {}
    for m in (per_layer if trace else e2e):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(s.device, memory_peak_bytes=memory_peak)
    if trace and win["trace"] is not None:
        device["busy_s"] = win["trace"]["busy_s"]
        device["window_s"] = win["trace"]["window_s"]
    checks = checked["checks"]
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": ctx.attempted,
              "failed": checks["unanswered_events"]["value"],
              "metrics": metrics, "device": device}
    if trace and win["trace"] is not None:
        result["breakdown"] = win["trace"]["breakdown"]
    result["checks"] = checks
    details = dict(workload=workload, seed=seed, seconds=seconds,
                   trace=trace, setup_s=s.setup_s,
                   setup_split=s.setup_split, warm=s.warmed,
                   answered_in_window=ctx.answered_in_window,
                   answers_spread=checked["answers_spread"],
                   **window_details(win))
    return {"result": result, "details": details, "ctx": ctx,
            "raw_trace": win["raw_trace"]}


def kernel_calls_us(trace: Optional[dict]) -> list:
    """Device times (us) of the served kernel's calls in the traced window."""
    if trace is None:
        return []
    return [d for name, calls in trace["op_calls_us"].items()
            if is_kernel(name) for d in calls]


def is_kernel(op_name: str) -> bool:
    """A served kernel by its name: on the TPU a Pallas kernel is an HLO
    ``custom-call`` (``tpu_custom_call``), and the trace names each device
    operation by its instruction's text; elsewhere an unnamed custom call
    is ``custom-call.<n>``."""
    import tracereduce
    return (tracereduce.opcode(op_name) == "custom-call"
            or op_name.startswith("custom-call"))
