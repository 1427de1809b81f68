"""μs-scale jet-tagging serving driver — the paper's deployment scenario.

Trains a small MLP or DeepSets tagger on the synthetic jet stream, quantizes
it to the paper's INT8 power-of-two scheme, deploys it behind a
``FleetServer`` running the FUSED cascade Pallas kernel (compiled on a TPU;
interpret mode only where the backend is a CPU, see
:mod:`repro.launch.platform`), and reports:

  * the device it serves on (platform, device kind, device count),
  * classification accuracy float vs INT8 (quantization cost),
  * measured wall-clock latency percentiles, labelled with the device, for
    a micro-batched stream and for single events sent one at a time,
  * the Tier-B modeled latency on the TPU target (fused vs per-layer),
  * the Tier-A μ-ORCA modeled latency for the same network on the VEK280
    (the paper's own deployment target).

Multi-tenant serving (beyond the paper — see repro.core.tenancy): with
``--replicas N`` each model gets N replica kernels; ``--mix a,b`` deploys
several models side by side, the software analogue of packing tenant
rectangles onto the shared AIE array. Events are dispatched *micro-batched*:
sliced across replicas, scattered, gathered back with batched percentiles.
The driver then also reports the Tier-A modeled multi-tenant schedule
(replica packing, shared PLIO budget) with both the serial R/latency
events/sec and the pipelined headline — initiation interval II, sustained
events/sec, and the contended pipelined throughput-frontier point the
deployment should be measured against.

Open-loop load and SLOs (the observatory half): ``--arrivals`` replaces
the back-to-back batched dispatch with a seeded wall-clock arrival
process offered through the fleet's admission control (offered vs
admitted vs shed counters, queue-wait histograms); ``--slo`` attaches
per-tenant SLOs — p99 latency budget in us plus an availability target —
with windowed error-budget accounting and multi-window burn-rate alerts.
The driver exits 1 when any tenant's error budget is exhausted, and
``--slo-report-out`` persists the cross-tenant ``SLOReport`` JSON.

    PYTHONPATH=src python -m repro.launch.serve --model deepsets-32 --events 256
    PYTHONPATH=src python -m repro.launch.serve --replicas 4
    PYTHONPATH=src python -m repro.launch.serve --mix deepsets-32,jsc-m --replicas 2
    PYTHONPATH=src python -m repro.launch.serve --replicas 2 \\
        --arrivals poisson:200 --slo 50000:0.95 --slo-report-out slo.json
"""
from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layerspec
from repro.data import JetConfig, jet_batch
from repro.launch import platform
from repro.models import deepsets as ds
from repro.models import mlp as mlp_lib
from repro.serve import ServeStats
from repro.serve.fleet import FleetServer, TenantSpec

MODELS = {
    "jsc-m": dict(kind="mlp", M=64, F=16, nodes=[64, 32, 32, 32, 5]),
    "jsc-xl": dict(kind="mlp", M=64, F=16, nodes=[128, 64, 64, 64, 5]),
    "deepsets-32": dict(kind="deepsets", M=32, F=21,
                        phi=[32, 32, 32], rho=[32, 10]),
    "deepsets-64": dict(kind="deepsets", M=64, F=21,
                        phi=[64, 64, 64], rho=[64, 10]),
}
#: Events per tenant sent one at a time after the micro-batched stream.
SINGLE_EVENTS = 8
SPECS = {"jsc-m": layerspec.jsc_m, "jsc-xl": layerspec.jsc_xl,
         "deepsets-32": layerspec.deepsets_32,
         "deepsets-64": layerspec.deepsets_64}


def _train(kind, M, F, n_classes, *, nodes=None, phi=None, rho=None,
           steps=300, seed=0):
    jc = JetConfig(n_particles=M, n_features=F, n_classes=n_classes,
                   seed=seed)
    key = jax.random.key(seed)
    if kind == "mlp":
        params = mlp_lib.mlp_init(key, F, nodes)
        loss_fn = mlp_lib.mlp_loss
    else:
        params = ds.deepsets_init(key, F, phi, rho)
        loss_fn = ds.deepsets_loss
    vg = jax.jit(jax.value_and_grad(loss_fn))
    lr = 2e-2
    for step in range(steps):
        x, y = jet_batch(jc, 256, step + 1)
        l, g = vg(params, jnp.asarray(x), jnp.asarray(y))
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
        if (step + 1) % 100 == 0:
            print(f"[serve] train step {step + 1}: loss {float(l):.4f}")
    return params, jc


def _accuracy(fn, jc, n=2048, seed=777):
    x, y = jet_batch(jc, n, seed)
    pred = np.argmax(np.asarray(fn(jnp.asarray(x))), axis=-1)
    return float((pred == y).mean())


def _prepare(name: str, *, train_steps: int, replicas: int, mode: str) -> dict:
    """Train + quantize one model; return its TenantSpec and eval context."""
    m = MODELS[name]
    n_classes = (m["nodes"][-1] if m["kind"] == "mlp" else m["rho"][-1])
    params, jc = _train(m["kind"], m["M"], m["F"], n_classes,
                        nodes=m.get("nodes"), phi=m.get("phi"),
                        rho=m.get("rho"), steps=train_steps)
    xcal, _ = jet_batch(jc, 512, 12345)
    if m["kind"] == "mlp":
        qmlp = mlp_lib.to_quantized(params, xcal)
        f_fn = jax.jit(lambda x: jnp.mean(mlp_lib.mlp_forward(params, x),
                                          axis=1))
        tenant = TenantSpec(name=name, qmlp=qmlp, mode=mode,
                            replicas=replicas, model_spec=SPECS[name]())
        e_in = qmlp.e_in
    else:
        qphi, qrho = ds.to_quantized(params, xcal)
        f_fn = jax.jit(lambda x: ds.deepsets_forward(params, x))
        tenant = TenantSpec(name=name, qmlp=qphi, rho=qrho, agg="mean",
                            mode=mode, replicas=replicas,
                            model_spec=SPECS[name]())
        e_in = qphi.e_in
    return dict(tenant=tenant, jc=jc, e_in=e_in, n_classes=n_classes,
                acc_float=_accuracy(f_fn, jc))


def _report_telemetry(fleet: FleetServer, snap: dict, args) -> None:
    """Persist the metrics snapshot and print the end-of-run summary."""
    drift = snap.get("drift", {})
    if args.metrics_out:
        fleet.registry.save(args.metrics_out,
                            extra={"drift": drift, "serve": snap["serve"]})
        print(f"[fleet] metrics: {len(fleet.registry.all())} series -> "
              f"{args.metrics_out}")
    for name, s in snap["serve"]["tenants"].items():
        if "rolling_p50_us" in s:
            print(f"[fleet] {name} rolling latency: "
                  f"p50 {s['rolling_p50_us']:.0f} us, "
                  f"p90 {s['rolling_p90_us']:.0f} us, "
                  f"p99 {s['rolling_p99_us']:.0f} us (streaming histogram)")
    overheads = fleet.registry.all("fleet.dispatch.overhead_us")
    if overheads:
        worst = max(h.quantile(0.99) for h in overheads if h.count)
        print(f"[fleet] dispatch overhead p99: {worst:.1f} us "
              f"({sum(h.count for h in overheads)} dispatches)")
    for metric in sorted(drift):
        d = drift[metric]
        mape = d.get("mape")
        if mape is None:
            continue
        tag = ("gateable Tier-A-vs-Tier-S" if metric.startswith("model.")
               else "informational wall-clock-vs-modeled")
        print(f"[fleet] drift {metric}: MAPE {100 * mape:.2f}% over "
              f"{len(d['entries'])} entr(ies) [{tag}]")


def _check_drift_gate(snap: dict, gate: float) -> None:
    """Exit nonzero when the model-path (Tier-A vs Tier-S) MAPE exceeds the
    gate. serve.* drift is never gated: it compares wall clock measured on
    whatever device serves (a TPU, or a CPU interpreting the kernels) with
    the modeled VEK280, so it tracks relative drift, not accuracy."""
    drift = {m: d for m, d in snap.get("drift", {}).items()
             if m.startswith("model.") and d.get("mape") is not None}
    if not drift:
        raise SystemExit("[fleet] drift gate: no model.* drift entries "
                         "populated (missing model_spec?)")
    worst = max(d["mape"] for d in drift.values())
    ok = worst <= gate
    print(f"[fleet] drift gate: worst model-path MAPE {100 * worst:.2f}% "
          f"vs threshold {100 * gate:.2f}% -> {'PASS' if ok else 'FAIL'}")
    if not ok:
        # Localize before failing: name the drifted entries and, for
        # model.stage.* metrics, the overhead constants they implicate.
        for m, d in sorted(drift.items(), key=lambda kv: -kv[1]["mape"]):
            if d["mape"] <= gate:
                continue
            flagged = d.get("flagged") or list(d.get("entries", {}))
            line = (f"[fleet] drift gate: {m} MAPE {100 * d['mape']:.2f}% "
                    f"— flagged {flagged}")
            if d.get("suspects"):
                line += f", suspect constants {d['suspects']}"
            print(line)
        raise SystemExit(1)


def _drive_open_loop(fleet: FleetServer, name: str, prep: dict, xq, y,
                     args) -> dict:
    """Offer the tenant's event stream on the --arrivals schedule."""
    from repro.serve import workload
    spec = args.arrival_spec
    dr = workload.drive(fleet, list(xq), spec, tenant=name, seed=args.seed)
    outputs = [r.wait(timeout=120) for r in dr.requests]
    print(f"[fleet] {name}: {spec.describe()} -> offered {dr.offered} "
          f"({dr.offered_eps:.0f}/s), admitted {dr.admitted}, "
          f"shed {dr.shed}, driver lag {dr.lag_s * 1e3:.1f} ms")
    if dr.requests:
        adm = np.asarray(dr.admitted_idx)
        preds = np.array([int(np.argmax(r.result[..., :prep["n_classes"]]))
                          for r in dr.requests])
        acc_q = float((preds == y[adm]).mean())
        lats = np.array([r.latency_us for r in dr.requests])
        waits = np.array([r.queue_wait_us for r in dr.requests])
        print(f"[fleet] {name}: float acc {prep['acc_float']:.3f}, "
              f"INT8 acc {acc_q:.3f} (admitted events)")
        print(f"[fleet] {name}: open-loop p50 "
              f"{float(np.percentile(lats, 50)):.0f} us, p99 "
              f"{float(np.percentile(lats, 99)):.0f} us; queue wait p50 "
              f"{float(np.percentile(waits, 50)):.0f} us, p99 "
              f"{float(np.percentile(waits, 99)):.0f} us "
              f"[{args.device}]")
    return {"inputs": xq[np.asarray(dr.admitted_idx, dtype=int)],
            "outputs": np.stack(outputs) if outputs else np.empty((0,))}


def _report_slo(fleet: FleetServer, args) -> "object":
    """Print each tenant's budget state; persist and return the SLOReport."""
    report = fleet.slo_snapshot()
    for name, s in report.tenants.items():
        spec = s["spec"]
        state = "EXHAUSTED" if s["exhausted"] else "ok"
        print(f"[slo] {name}: p99 budget {spec['p99_latency_budget_ns'] / 1e3:.0f} us"
              f" @ {spec['availability']:.3g} availability | "
              f"good {s['good']}, bad {s['bad']}, shed {s['shed']} | "
              f"burn rate {s['burn_rate_window']:.2f}x, budget remaining "
              f"{100 * s['error_budget_remaining']:.1f}% [{state}]")
        for a in s["alerts"]:
            print(f"[slo] {name}: ALERT {a['severity']} — burn "
                  f"{a['burn_long']:.1f}x/{a['burn_short']:.1f}x over "
                  f"{a['long_s']:g}s/{a['short_s']:g}s windows "
                  f"(threshold {a['threshold']:g}x)")
    if args.slo_report_out:
        report.save(args.slo_report_out)
        print(f"[slo] report -> {args.slo_report_out}")
    return report


def _serve_closed_loop(fleet: FleetServer, name: str, prep: dict, xq, y,
                       args) -> dict:
    """Serve ``args.events`` micro-batched, then a few single events."""
    # Micro-batched dispatch: the event stream is sliced across the
    # tenant's replicas (scatter), each slice rides one replica's batching
    # window as a single kernel launch, results gather back in submission
    # order — replicas run concurrently back to back instead of one round
    # trip per event.
    xb, xs = xq[:args.events], xq[args.events:]
    br = fleet.infer_batch(xb, tenant=name, timeout=120)
    preds = np.array([int(np.argmax(r[..., :prep["n_classes"]]))
                      for r in br.results])
    acc_q = float((preds == y[:args.events]).mean())
    print(f"[fleet] {name}: float acc {prep['acc_float']:.3f}, "
          f"INT8 acc {acc_q:.3f}")
    print(f"[fleet] {name}: batched p50 {br.percentile(50):.0f} us, "
          f"p99 {br.percentile(99):.0f} us, "
          f"{br.throughput_eps:.0f} events/s over "
          f"{len(br.replica_counts)} replicas "
          f"(scatter {br.replica_counts}, total {br.n}) [{args.device}]")
    # The trigger-stream case: each event waits for its answer before the
    # next is sent, so every one is a batch of one.
    single = ServeStats()
    outputs = []
    for x in xs:
        req = fleet.submit(x, tenant=name)
        outputs.append(req.wait(timeout=120))
        single.record(req.t_submit, req.t_done)
    if xs.size:
        print(f"[fleet] {name}: single events p50 "
              f"{single.percentile(50):.0f} us, p99 "
              f"{single.percentile(99):.0f} us over {len(xs)} sent one at "
              f"a time [{args.device}]")
    mdl = fleet._servers[name][0].modeled_latency_us()
    print(f"[fleet] {name}: modeled TPU-v5e latency: fused "
          f"{mdl['fused_us']:.2f} us vs per-layer {mdl['unfused_us']:.2f} us"
          f" ({mdl['speedup']:.2f}x from cascade-analogue fusion)")
    return {"inputs": xq, "outputs": np.concatenate(
                [br.results, np.stack(outputs)]) if outputs else br.results,
            "batch": br, "single": single}


def _serve_fleet(preps: dict, args) -> dict:
    """Multi-tenant deployment: FleetServer over R replicas per tenant.

    Returns, per tenant, the quantized inputs served, the outputs in the
    same order, the tenant's spec and the replicas' jitted model function.
    """
    tracer = None
    if args.trace_out:
        # Cycle-clock lanes of a short Tier-S run per tenant; the served
        # path's spans are in the profiler's trace.
        from repro.sim.trace import ChromeTrace
        tracer = ChromeTrace(meta={"driver": "serve",
                                   "mix": ",".join(preps),
                                   "policy": args.policy})
    fleet = FleetServer([p["tenant"] for p in preps.values()],
                        policy=args.policy,
                        slos=args.slo_specs,
                        admission_depth=args.admission_depth)
    print(f"\n[fleet] {fleet.num_replicas} replicas across "
          f"{len(preps)} tenant(s), policy={args.policy}")
    open_loop = (args.arrival_spec is not None
                 and args.arrival_spec.open_loop)
    results = {}
    try:
        for name, prep in preps.items():
            n = args.events + (0 if open_loop else SINGLE_EVENTS)
            x, y = jet_batch(prep["jc"], n, 999)
            xq = np.clip(np.round(x / 2.0 ** prep["e_in"]), -128,
                         127).astype(np.int8)
            # Open-loop: events are *offered* on the arrival schedule and
            # the fleet's admission control decides admitted vs shed.
            drive = _drive_open_loop if open_loop else _serve_closed_loop
            results[name] = drive(fleet, name, prep, xq, y, args)
            results[name].update(tenant=prep["tenant"],
                                 fn=fleet._servers[name][0]._fn)
        modeled = fleet.modeled_throughput()
        telemetry = (fleet.telemetry_snapshot()
                     if (args.metrics_out or args.trace_out
                         or args.drift_gate is not None) else None)
        if tracer is not None:
            from repro.sim import run as simrun
            for name in preps:
                design = fleet._design(name)
                if design is not None:
                    simrun.simulate_placement(
                        design.placement, tenant=name,
                        config=simrun.SimConfig(events=2), tracer=tracer)
            tracer.save(args.trace_out)
            print(f"[fleet] Tier-S trace: {len(tracer.spans())} spans "
                  f"-> {args.trace_out}")
    finally:
        fleet.close()
    if telemetry is not None:
        _report_telemetry(fleet, telemetry, args)
    for name, m in modeled.items():
        if name == "_fleet":
            print(f"[fleet] Tier-A schedule on VEK280: {m['instances']} "
                  f"instances, {m['tiles']} tiles "
                  f"({100 * m['utilization']:.0f}% of array), "
                  f"{m['plio_ports']} PLIO ports, "
                  f"{m['modeled_eps'] / 1e6:.2f} Meps serial / "
                  f"{m['modeled_eps_pipelined_contended'] / 1e6:.2f} Meps "
                  f"pipelined contended")
        else:
            print(f"[fleet] Tier-A {name}: {m['replicas']} replicas @ "
                  f"{m['latency_ns']:.0f} ns -> "
                  f"{m['events_per_sec'] / 1e6:.2f} Meps serial "
                  f"(feasible={m['feasible']})")
            if "interval_ns" in m:
                print(f"[fleet] Tier-A {name} pipelined: II "
                      f"{m['interval_ns']:.0f} ns -> "
                      f"{m['events_per_sec_pipelined'] / 1e6:.2f} Meps free, "
                      f"{m.get('events_per_sec_pipelined_contended', 0.0) / 1e6:.2f}"
                      f" Meps shim-contended")
            fp = m.get("frontier_point")
            if fp:
                print(f"[fleet] Tier-A {name} frontier target: "
                      f"{fp['replicas']} replicas @ {fp['latency_ns']:.0f} ns"
                      f" / II {fp['interval_ns']:.0f} ns -> "
                      f"{fp['events_per_sec_pipelined_contended'] / 1e6:.2f} "
                      f"Meps sustained ({fp['contention']} contention)")
    if args.drift_gate is not None and telemetry is not None:
        _check_drift_gate(telemetry, args.drift_gate)
    if fleet.slo_trackers:
        report = _report_slo(fleet, args)
        if not report.ok:
            print(f"[slo] error budget exhausted for "
                  f"{report.exhausted_tenants} -> exit 1")
            raise SystemExit(report.exit_code())
    return results


def main(argv: Optional[Sequence[str]] = None) -> dict:
    """Run the driver; return ``{tenant: results}`` (see ``_serve_fleet``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=list(MODELS), default="deepsets-32")
    ap.add_argument("--mix", type=str, default=None,
                    help="comma-separated model names served side by side "
                         "(overrides --model)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica kernels per tenant")
    ap.add_argument("--policy", choices=["rr", "least_loaded"],
                    default="least_loaded")
    ap.add_argument("--events", type=int, default=256)
    ap.add_argument("--train-steps", type=int, default=300)
    ap.add_argument("--mode", choices=["fused", "unfused"], default="fused")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="write the fleet's metrics-registry snapshot "
                         "(queue depths, dispatch overheads, rolling "
                         "percentiles, drift ratios) as JSON")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write a Chrome trace of a short Tier-S sim per "
                         "tenant; the served path's spans (fleet.*, "
                         "serve.*) are in the profiler trace "
                         "(jax.profiler.start_trace)")
    ap.add_argument("--drift-gate", type=float, default=None,
                    help="fail (exit 1) when the Tier-A-vs-Tier-S model-path "
                         "drift MAPE exceeds this fraction (e.g. 0.05)")
    ap.add_argument("--arrivals", type=str, default=None,
                    help="open-loop arrival process (same grammar as "
                         "repro.launch.simulate): closed | poisson:<eps> | "
                         "burst:<eps>[:<cv>] | trace:<file>; rates are "
                         "wall-clock events/sec on this host")
    ap.add_argument("--seed", type=int, default=0,
                    help="arrival RNG seed (reproducible --arrivals runs)")
    ap.add_argument("--slo", type=str, default=None,
                    help="per-tenant SLOs: <p99_us>[:<avail>] for every "
                         "tenant or name=<p99_us>[:<avail>],... ; the driver "
                         "exits 1 when any tenant's error budget is "
                         "exhausted")
    ap.add_argument("--slo-window", type=float, default=60.0,
                    help="SLO error-budget accounting window in seconds")
    ap.add_argument("--slo-report-out", type=str, default=None,
                    help="write the cross-tenant SLOReport JSON")
    ap.add_argument("--admission-depth", type=int, default=None,
                    help="shed offered events when every replica queue is "
                         "at/above this depth (None = never shed)")
    args = ap.parse_args(argv)
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")

    names = ([s.strip() for s in args.mix.split(",") if s.strip()]
             if args.mix else [args.model])
    for n in names:
        if n not in MODELS:
            ap.error(f"unknown model {n!r} (choices: {list(MODELS)})")
    if len(set(names)) != len(names):
        ap.error(f"--mix has duplicate model names: {names}")

    args.arrival_spec = None
    if args.arrivals:
        from repro.serve import workload
        try:
            args.arrival_spec = workload.parse_arrivals(args.arrivals)
        except (ValueError, OSError) as exc:
            ap.error(str(exc))
    args.slo_specs = None
    if args.slo:
        from repro.obs.slo import parse_slo
        try:
            # budgets typed in us (the wall-clock unit the driver prints)
            args.slo_specs = parse_slo(args.slo, names, budget_scale_ns=1e3,
                                       window_s=args.slo_window)
        except ValueError as exc:
            ap.error(str(exc))

    cache = platform.enable_compile_cache()
    dev = platform.device_info()
    args.device = f"{dev['platform']} {dev['kind']} x{dev['count']}"
    kernels = ("interpreted (CPU backend)" if platform.interpret()
               else "compiled")
    print(f"[serve] device: {args.device}; Pallas kernels {kernels}; "
          f"compile cache {cache}")
    preps = {n: _prepare(n, train_steps=args.train_steps,
                         replicas=args.replicas, mode=args.mode)
             for n in names}
    return _serve_fleet(preps, args)


if __name__ == "__main__":
    main()
