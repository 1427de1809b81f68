"""DeepSets tagger: phi per constituent, mean over the set, rho per event.

Served by the program's fused ``deepsets`` kernel. An event is a set of
``constituents`` x ``features`` int8 values; the answer is (1, classes) int8.
"""
from __future__ import annotations

import numpy as np

import intquant
import jets


def build(cfg: dict, seed: int) -> dict:
    """The int8 weights, calibrated on a seeded jet batch."""
    rng = np.random.default_rng([seed, 1])
    m, f = cfg["constituents"], cfg["features"]
    phi_w, phi_b = intquant.float_mlp(rng, f, cfg["phi"])
    rho_w, rho_b = intquant.float_mlp(rng, cfg["phi"][-1], cfg["rho"])
    cal = jets.jet_batch(m, f, cfg["calibration_events"], seed=[seed, 2])
    e_in, phi = intquant.quantize_mlp(phi_w, phi_b, [True] * len(phi_w),
                                      cal.reshape(-1, f))
    h = cal.astype(np.float64)
    for w, b in zip(phi_w, phi_b):
        h = np.maximum(h @ w + b, 0.0)
    _, rho = intquant.quantize_mlp(
        rho_w, rho_b, [i < len(rho_w) - 1 for i in range(len(rho_w))],
        h.sum(axis=1) / m, e_in=phi[-1].e_out)
    return {"e_in": e_in, "phi": phi, "rho": rho}


def tenant(cfg: dict, model: dict, name: str):
    """The program's ``TenantSpec`` serving these weights."""
    from repro.serve.fleet import TenantSpec
    from program_weights import quantized_mlp
    return TenantSpec(name=name, qmlp=quantized_mlp(model["e_in"],
                                                    model["phi"]),
                      rho=quantized_mlp(model["phi"][-1].e_out, model["rho"]),
                      agg="mean")


def inputs(cfg: dict, model: dict, n: int, seed: int) -> np.ndarray:
    x = jets.jet_batch(cfg["constituents"], cfg["features"], n, seed=seed)
    return intquant.quantize(x, model["e_in"])


def reference(cfg: dict, model: dict, x: np.ndarray, *,
              int4: bool = False) -> np.ndarray:
    """(n, 1, classes) int8 for (n, constituents, features) int8."""
    h = intquant.mlp(x, model["phi"], int4=int4)
    return intquant.mlp(intquant.mean_over_set(h), model["rho"], int4=int4)


def macs_per_event(cfg: dict) -> int:
    m, k, macs = cfg["constituents"], cfg["features"], 0
    for n in cfg["phi"]:
        macs += m * k * n
        k = n
    macs += m * k                      # the mean, as a ones-row product
    for n in cfg["rho"]:
        macs += k * n
        k = n
    return macs


def min_bytes(cfg: dict, batch: int) -> int:
    """Input, weights (int8), biases (int32) and output, each moved once."""
    k, w = cfg["features"], 0
    for n in list(cfg["phi"]) + list(cfg["rho"]):
        w += k * n + 4 * n
        k = n
    event_in = cfg["constituents"] * cfg["features"]
    return batch * (event_in + cfg["rho"][-1]) + w
