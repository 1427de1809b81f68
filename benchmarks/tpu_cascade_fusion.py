"""Tier-B: the cascade mechanism on TPU — fused single-kernel MLP vs
per-layer kernel chain.

Quantifies exactly what the paper's cascade eliminates, in TPU terms:
  * HBM bytes moved per inference (intermediates stay in VMEM when fused),
  * kernel launches (1 vs L),
  * modeled end-to-end latency on the v5e target (overhead-aware model),
  * equality of the fused kernel's outputs with the oracle (bit-exact
    INT8), compiled on a TPU and interpreted on a CPU.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import tpu_model
from repro.core.fusion_planner import plan, shapes_from_model
from repro.core.layerspec import REALISTIC_WORKLOADS, synthetic_mlp
from repro.kernels.cascade_mlp import cascade_mlp, cascade_mlp_ref, mlp_unfused
from repro.launch import platform
from repro.quant import quantize_mlp


def _make_qmlp(sizes, M, seed=0):
    rng = np.random.default_rng(seed)
    weights, biases = [], []
    k = sizes[0]
    for n in sizes[1:]:
        weights.append(rng.normal(0, 0.5 / np.sqrt(k), (k, n)))
        biases.append(rng.normal(0, 0.1, n))
        k = n
    relus = [True] * (len(weights) - 1) + [False]
    x = rng.normal(0, 1.0, (M, sizes[0]))
    return quantize_mlp(weights, biases, relus, x), x


def main() -> dict:
    res = {}
    print("workload,hbm_fused_B,hbm_unfused_B,launches_fused,launches_unfused,"
          "modeled_fused_us,modeled_unfused_us,speedup,bit_exact")
    for name, ly in (("JSC-M", [16, 64, 32, 32, 32, 5]),
                     ("JSC-XL", [16, 128, 64, 64, 64, 5]),
                     ("64^3L8", [64] * 9)):
        M = 64
        qmlp, xf = _make_qmlp(ly, M)
        shapes = [tpu_model.LayerShape(M=M, K=l.w_q.shape[0],
                                       N=l.w_q.shape[1])
                  for l in qmlp.layers]
        hbm_f = tpu_model.hbm_traffic_bytes(shapes, fused=True)
        hbm_u = tpu_model.hbm_traffic_bytes(shapes, fused=False)
        t_f = tpu_model.fused_chain_time_s(shapes) * 1e6
        t_u = tpu_model.unfused_chain_time_s(shapes) * 1e6
        xq = jnp.clip(jnp.round(jnp.asarray(xf) / 2.0 ** qmlp.e_in),
                      -128, 127).astype(jnp.int8)
        fused_out = cascade_mlp(xq, qmlp, interpret=platform.interpret())
        ref_out = cascade_mlp_ref(xq, qmlp)
        exact = bool(jnp.all(fused_out == ref_out))
        print(f"{name},{hbm_f},{hbm_u},1,{len(shapes)},"
              f"{t_f:.2f},{t_u:.2f},{t_u / t_f:.2f}x,{exact}")
        res[f"speedup_{name}"] = t_u / t_f
        res[f"hbm_reduction_{name}"] = hbm_u / hbm_f
        assert exact, f"{name}: fused kernel diverged from oracle"
    # fusion-planner decision quality on every realistic workload
    for name, fn in REALISTIC_WORKLOADS.items():
        p = plan(shapes_from_model(fn()))
        res[f"plan_kernels_{name}"] = p.n_kernels
        print(f"fusion-plan {name}: {p.n_kernels} kernel(s), "
              f"modeled speedup {p.speedup:.2f}x vs per-layer")
    return res


if __name__ == "__main__":
    main()
