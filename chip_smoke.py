"""Smoke test of the served path on one TPU chip.

Serves Deepsets-32 (the fused DeepSets kernel) and JSC-M (the fused cascade
MLP kernel) at their published widths through ``repro.launch.serve.main``,
the same ``FleetServer`` path a user runs: each model is trained for a few
steps on the chip from a fixed seed, quantized to INT8, and answers a
micro-batched stream plus a few single events. The script then checks that

  * every served output equals the jnp reference (``deepsets_ref`` /
    ``cascade_mlp_ref``) on the same quantized weights, with ``==``;
  * the served jitted function lowers to a Mosaic kernel
    (``tpu_custom_call``), so the kernel was compiled, not interpreted.

It prints the device, the compile cache directory, the seconds JAX spent
compiling for each model (persistent-cache reads included, so a second run
reads lower), the events answered and matched, and wall-clock percentiles.
The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check, or a backend other than a TPU, exits non-zero without it.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import pathlib
import sys

SRC = pathlib.Path(__file__).resolve().parent / "src"
MODELS = ("deepsets-32", "jsc-m")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Sums JAX's backend compile seconds and counts persistent-cache hits
    while the context is open (JAX times a cache read as a compile)."""

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


def _reference(tenant):
    """The jnp oracle for a served tenant, on its quantized weights."""
    from repro.kernels.cascade_mlp import cascade_mlp_ref, deepsets_ref
    if tenant.rho is None:
        return lambda x: cascade_mlp_ref(x, tenant.qmlp)
    return lambda x: deepsets_ref(x, tenant.qmlp, tenant.rho, agg=tenant.agg)


def run(models=MODELS, *, events: int = 64, train_steps: int = 20) -> list:
    """Serve each model through ``serve.main`` and check it; one report each.

    A report's ``failures`` lists every check that did not hold. The kernel
    check expects ``tpu_custom_call`` exactly when the platform compiles
    Pallas kernels, so the same body runs on a CPU in interpret mode.
    """
    import jax
    import numpy as np

    from repro.launch import platform, serve

    reports = []
    for name in models:
        with CompileClock() as clock:
            res = serve.main(["--model", name, "--events", str(events),
                              "--train-steps", str(train_steps)])[name]
        xs, got = res["inputs"], res["outputs"]
        want = np.asarray(jax.jit(jax.vmap(_reference(res["tenant"])))(xs))
        exact = int(np.sum(np.all((got == want).reshape(len(xs), -1),
                                  axis=1))) if got.shape == want.shape else 0
        lowered = res["fn"].lower(xs[:events]).as_text()
        kernel = "tpu_custom_call" in lowered
        failures = []
        if res["batch"].n < events:
            failures.append(f"{res['batch'].n} of {events} batched events "
                            f"answered")
        if got.dtype != np.int8 or exact != len(xs):
            failures.append(f"{exact} of {len(xs)} outputs equal the "
                            f"reference (dtype {got.dtype})")
        if kernel == platform.interpret():
            failures.append(f"tpu_custom_call {'present' if kernel else 'absent'}"
                            f" with interpret={platform.interpret()}")
        reports.append({
            "model": name, "events": len(xs), "exact": exact,
            "tpu_custom_call": kernel, "compile_s": clock.seconds,
            "compiles": clock.compiles, "cache_hits": clock.cache_hits,
            "batch_p50_us": res["batch"].percentile(50),
            "batch_p99_us": res["batch"].percentile(99),
            "single_p50_us": res["single"].percentile(50),
            "single_p99_us": res["single"].percentile(99),
            "failures": failures})
    return reports


def main() -> int:
    import jax

    found = jax.devices()[0].platform
    if found != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {found!r}",
              file=sys.stderr)
        return 1
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is missing under {SRC}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    from repro.launch import platform

    print(f"[smoke] compile cache: {platform.enable_compile_cache()}")
    device = platform.device_info()
    print(f"[smoke] device: {device}")
    reports = run()
    for r in reports:
        print(f"[smoke] {r['model']}: {r['events']} events answered, "
              f"{r['exact']} equal to the reference, tpu_custom_call "
              f"{r['tpu_custom_call']}; compile {r['compile_s']:.3f} s over "
              f"{r['compiles']} compiles ({r['cache_hits']} cache hits); "
              f"batched p50 {r['batch_p50_us']:.1f} us p99 "
              f"{r['batch_p99_us']:.1f} us, single p50 "
              f"{r['single_p50_us']:.1f} us p99 {r['single_p99_us']:.1f} us "
              f"[{device['kind']}]")
    failures = [f"{r['model']}: {f}" for r in reports for f in r["failures"]]
    for f in failures:
        print(f"chip_smoke: FAILED {f}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
