"""JSC MLP tagger: one MLP applied to every row of an event block.

Served by the program's fused ``cascade_mlp`` kernel. An event is the
paper's block of ``rows`` x ``features`` int8 values (M = 64 jets of 16
features for JSC-M); the answer is (rows, classes) int8.
"""
from __future__ import annotations

import numpy as np

import intquant
import jets

#: The ``repro.serve`` function that serves this kind; the fault tests wrap it.
FAULT_SITE = "cascade_mlp"


def build(cfg: dict, seed: int) -> dict:
    rng = np.random.default_rng([seed, 1])
    ws, bs = intquant.float_mlp(rng, cfg["features"], cfg["nodes"])
    cal = jets.jet_batch(cfg["rows"], cfg["features"],
                         cfg["calibration_events"], seed=[seed, 2])
    e_in, layers = intquant.quantize_mlp(
        ws, bs, [i < len(ws) - 1 for i in range(len(ws))],
        cal.reshape(-1, cfg["features"]))
    return {"e_in": e_in, "layers": layers}


def tenant(cfg: dict, model: dict, name: str):
    from repro.serve.fleet import TenantSpec
    from program_weights import quantized_mlp
    return TenantSpec(name=name,
                      qmlp=quantized_mlp(model["e_in"], model["layers"]))


def inputs(cfg: dict, model: dict, n: int, seed: int) -> np.ndarray:
    x = jets.jet_batch(cfg["rows"], cfg["features"], n, seed=seed)
    return intquant.quantize(x, model["e_in"])


def reference(cfg: dict, model: dict, x: np.ndarray, *,
              int4: bool = False) -> np.ndarray:
    """(n, rows, classes) int8 for (n, rows, features) int8."""
    return intquant.mlp(x, model["layers"], int4=int4)


def control(cfg: dict, model: dict, x: np.ndarray) -> np.ndarray:
    """The reference on the int4 grid, one precision below int8."""
    return reference(cfg, model, x, int4=True)


def macs_per_event(cfg: dict) -> int:
    k, macs = cfg["features"], 0
    for n in cfg["nodes"]:
        macs += cfg["rows"] * k * n
        k = n
    return macs


def min_bytes(cfg: dict, batch: int) -> int:
    k, w = cfg["features"], 0
    for n in cfg["nodes"]:
        w += k * n + 4 * n
        k = n
    return batch * cfg["rows"] * (cfg["features"] + cfg["nodes"][-1]) + w
