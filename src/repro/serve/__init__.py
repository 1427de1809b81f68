"""μs-scale inference serving runtime (the paper's deployment scenario).

The trigger-system setting: events arrive continuously; each must be
classified within a hard latency budget. The engine mirrors μ-ORCA's
execution model:

  * the whole model is compiled as ONE fused kernel (cascade analogue) —
    chosen by the VMEM fusion planner, with the per-layer chain as the
    explicit baseline;
  * requests are micro-batched within a bounded collection window (the
    PLIO-ingest analogue: batching amortizes the fixed ingest/launch
    overheads the paper's model makes explicit);
  * the engine reports measured wall-time percentiles AND the Tier-B
    overhead-aware latency estimate for the deployed TPU target.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tpu_model
from repro.core.fusion_planner import FusionPlan, plan
from repro.core.tpu_model import LayerShape
from repro.launch import platform
from repro.obs import span
from repro.quant import QuantizedMLP, quantize_pow2
from repro.kernels.cascade_mlp import (cascade_mlp, cascade_mlp_ref, deepsets,
                                       deepsets_ref, mlp_unfused)
from repro.models.part import part_forward, serving_params


@dataclasses.dataclass(frozen=True)
class ServedModel:
    """A served model other than the int8 MLP and DeepSets.

    ``fn(event, params)`` answers one event, the event first as every
    served function of this module takes it. :class:`JetServer` batches it
    with ``jax.vmap`` over events (``params`` shared) and compiles it with
    ``jax.jit`` for power-of-two batch sizes; ``params``, a pytree of
    arrays, are arguments of those programs and not constants compiled into
    them, so new weights need no new compile.
    """

    fn: Callable[[Any, Any], Any]
    params: Any


def part_jet(event, params):
    """One Particle Transformer jet: ``models.part.part_forward`` taking the
    event first, the order the served-model convention and the fault tests
    (which wrap this global) rely on."""
    return part_forward(params, event)


def part_model(params) -> ServedModel:
    """ParT served from float32 ``params`` (``models.part`` layout), its
    matrix weights held in bfloat16."""
    return ServedModel(fn=part_jet, params=serving_params(params))


@dataclasses.dataclass
class ServeStats:
    latencies_us: List[float] = dataclasses.field(default_factory=list)
    batch_sizes: List[int] = dataclasses.field(default_factory=list)
    t_first_submit: Optional[float] = None
    t_last_done: Optional[float] = None

    def record(self, t_submit: float, t_done: float) -> None:
        """Record one completed event and extend the serving window."""
        self.latencies_us.append((t_done - t_submit) * 1e6)
        if self.t_first_submit is None or t_submit < self.t_first_submit:
            self.t_first_submit = t_submit
        if self.t_last_done is None or t_done > self.t_last_done:
            self.t_last_done = t_done

    def percentile(self, p: float) -> float:
        if not self.latencies_us:
            return 0.0
        arr = np.asarray(self.latencies_us)
        # Interpolated tail percentiles under-report on small samples (p99 of
        # 4 events would land below the observed max); once fewer than one
        # sample sits above the requested rank, report the observed max.
        if p >= 50.0 and arr.size * (100.0 - p) < 100.0:
            return float(arr.max())
        return float(np.percentile(arr, p))

    def throughput_eps(self) -> float:
        """Measured events/sec over the first-submit .. last-done window."""
        if self.t_first_submit is None or self.t_last_done is None:
            return 0.0
        window_s = self.t_last_done - self.t_first_submit
        return len(self.latencies_us) / window_s if window_s > 0 else 0.0

    def summary(self) -> dict:
        return {"n": len(self.latencies_us),
                "p50_us": self.percentile(50), "p99_us": self.percentile(99),
                "throughput_eps": self.throughput_eps(),
                "mean_batch": (float(np.mean(self.batch_sizes))
                               if self.batch_sizes else 0.0)}


@dataclasses.dataclass
class _Request:
    x: np.ndarray
    t_submit: float
    event: threading.Event = dataclasses.field(default_factory=threading.Event)
    result: Optional[np.ndarray] = None
    t_done: Optional[float] = None
    t_start: Optional[float] = None
    """When the serving batch holding this request began executing; the gap
    from ``t_submit`` is the queue wait (collection window + backlog)."""
    error: Optional[Exception] = None
    """What the model function raised for this request's batch (a compile
    error, say); set instead of ``result`` and re-raised by the waiter."""

    def wait(self, timeout: float) -> np.ndarray:
        """Block until served; return the result or re-raise its error."""
        if not self.event.wait(timeout):
            raise TimeoutError("inference timed out")
        if self.error is not None:
            raise self.error
        return self.result

    @property
    def latency_us(self) -> float:
        return ((self.t_done - self.t_submit) * 1e6
                if self.t_done is not None else 0.0)

    @property
    def queue_wait_us(self) -> float:
        return ((self.t_start - self.t_submit) * 1e6
                if self.t_start is not None else 0.0)


class JetServer:
    """Batching inference server for jet taggers: a quantized MLP or
    DeepSets (``qmlp``, ``rho``), or any other :class:`ServedModel`
    (``model``); exactly one of ``qmlp`` and ``model``.

    ``mode`` (int8 taggers): 'fused' (single cascade kernel), 'unfused'
    (per-layer chain), 'ref' (pure-jnp oracle; used in tests for
    bit-identical checks). ``interpret`` defaults to what the platform needs
    (:func:`repro.launch.platform.interpret`): compiled kernels on a TPU.
    """

    def __init__(self, qmlp: Optional[QuantizedMLP] = None, *,
                 rho: Optional[QuantizedMLP] = None,
                 agg: str = "mean",
                 mode: str = "fused",
                 model: Optional[ServedModel] = None,
                 max_batch: int = 64,
                 window_us: float = 200.0,
                 interpret: Optional[bool] = None,
                 on_batch: Optional[Callable[[List[_Request]], None]] = None):
        if (qmlp is None) == (model is None):
            raise ValueError("give exactly one of qmlp and model")
        self.qmlp, self.rho, self.agg = qmlp, rho, agg
        self.model = model
        self.mode = mode
        self.max_batch = max_batch
        self.window_us = window_us
        self.interpret = (platform.interpret() if interpret is None
                          else interpret)
        self.on_batch = on_batch
        self.stats = ServeStats()
        self._q: "queue.Queue[_Request]" = queue.Queue()
        self._step = 0
        #: Batch sizes served so far: a new one compiles or loads a program.
        self._shapes: set = set()
        self._stop = threading.Event()
        self._fn = self._build()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    # -- model function -------------------------------------------------------
    def _build(self) -> Callable[[jnp.ndarray], jnp.ndarray]:
        if self.model is not None:
            return self._build_model()
        is_deepsets = self.rho is not None
        if is_deepsets:
            # DeepSets consumes one event (M, F) at a time; vmap batches events.
            if self.mode == "fused":
                f = lambda x: deepsets(x, self.qmlp, self.rho, agg=self.agg,
                                       interpret=self.interpret)
            else:
                f = lambda x: deepsets_ref(x, self.qmlp, self.rho, agg=self.agg)
            fn = jax.jit(jax.vmap(f))
        else:
            if self.mode == "fused":
                f = lambda x: cascade_mlp(x, self.qmlp,
                                          interpret=self.interpret)
            elif self.mode == "unfused":
                f = lambda x: mlp_unfused(x, self.qmlp,
                                          interpret=self.interpret)
            else:
                f = lambda x: cascade_mlp_ref(x, self.qmlp)
            fn = jax.jit(jax.vmap(f))
        return fn

    def _build_model(self) -> Callable[[jnp.ndarray], jnp.ndarray]:
        """A :class:`ServedModel`'s batched call. Its program is compiled
        for the power-of-two batch sizes up to ``max_batch`` (and
        ``max_batch``) alone: a batch between two is padded with zero events
        to the next and the answers sliced back, so a large model compiles
        a handful of programs, not one per batch size."""
        fn = jax.jit(jax.vmap(self.model.fn, in_axes=(0, None)))
        params = jax.device_put(self.model.params)
        sizes = sorted({min(1 << k, self.max_batch)
                        for k in range(self.max_batch.bit_length() + 1)})

        def call(xs: jnp.ndarray) -> jnp.ndarray:
            n = xs.shape[0]
            size = next(b for b in sizes if b >= n)
            if size == n:
                return fn(xs, params)
            pad = [(0, size - n)] + [(0, 0)] * (xs.ndim - 1)
            return fn(jnp.pad(xs, pad), params)[:n]
        return call

    # -- public API ------------------------------------------------------------
    def submit(self, x: np.ndarray) -> _Request:
        req = _Request(x=x, t_submit=time.perf_counter())
        self._q.put(req)
        return req

    def infer(self, x: np.ndarray, timeout: float = 30.0) -> np.ndarray:
        return self.submit(x).wait(timeout)

    def close(self):
        self._stop.set()
        self._thread.join(timeout=5)

    # -- batching loop ----------------------------------------------------------
    def _collect(self) -> List[_Request]:
        with span("serve.wait"):
            try:
                first = self._q.get(timeout=0.05)
            except queue.Empty:
                return []
        with span("serve.collect"):
            batch = [first]
            deadline = time.perf_counter() + self.window_us * 1e-6
            while len(batch) < self.max_batch:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    batch.append(self._q.get(timeout=remaining))
                except queue.Empty:
                    break
        return batch

    def _loop(self):
        while not self._stop.is_set():
            batch = self._collect()
            if not batch:
                continue
            t_start = time.perf_counter()
            self._step += 1
            size = len(batch)
            with span("serve.step", step=self._step, size=size,
                          new_shape=int(size not in self._shapes)):
                for r in batch:
                    r.t_start = t_start
                try:
                    with span("serve.to_device"):
                        xs = jnp.asarray(np.stack([r.x for r in batch]))
                    with span("serve.dispatch"):
                        ys = self._fn(xs)
                    with span("serve.to_host"):
                        out = np.asarray(ys)
                except Exception as exc:
                    # A worker that died here would leave every waiter to
                    # time out; hand the error (e.g. the kernel compiler's)
                    # to each.
                    for r in batch:
                        r.error = exc
                        r.event.set()
                    continue
                t_done = time.perf_counter()
                self._shapes.add(size)
                with span("serve.reply", events=size):
                    self._reply(batch, out, t_done)

    def _reply(self, batch: List[_Request], out: np.ndarray,
               t_done: float) -> None:
        for i, r in enumerate(batch):
            r.result = out[i]
            r.t_done = t_done
            self.stats.record(r.t_submit, t_done)
        self.stats.batch_sizes.append(len(batch))
        if self.on_batch is not None:
            # Telemetry is recorded before any waiter wakes, and must never
            # wedge the worker loop: a raising observer would strand every
            # waiter on this queue.
            try:
                self.on_batch(batch)
            except Exception:
                pass
        for r in batch:
            r.event.set()

    # -- Tier-B modeled latency on the TPU target --------------------------------
    def modeled_latency_us(self) -> dict:
        layers = [LayerShape(M=(self.qmlp.layers[0].w_q.shape[0] if self.rho
                                else 64), K=l.w_q.shape[0], N=l.w_q.shape[1])
                  for l in (list(self.qmlp.layers)
                            + (list(self.rho.layers) if self.rho else []))]
        fused = tpu_model.fused_chain_time_s(layers) * 1e6
        unfused = tpu_model.unfused_chain_time_s(layers) * 1e6
        return {"fused_us": fused, "unfused_us": unfused,
                "speedup": unfused / fused}
