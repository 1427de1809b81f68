"""Quickstart: the three layers of the repro framework in ~60 seconds.

  1. Tier A — the paper itself: run the μ-ORCA DSE on a jet-tagging model
     and read the overhead-aware latency estimate for the VEK280.
  2. Kernels — execute the fused cascade-MLP Pallas kernel (compiled on a
     TPU, interpreted on a CPU) and check it against the pure-jnp oracle
     bit-for-bit.
  3. Substrate — build one of the assigned LM architectures (reduced size),
     run a train step and a decode step.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax
import jax.numpy as jnp

# --- 1. Tier A: μ-ORCA DSE ---------------------------------------------------
from repro.core import dse, layerspec

model_spec = layerspec.deepsets_32()
result = dse.explore(model_spec)
print("[1] μ-ORCA DSE on Deepsets-32 (VEK280, 8x38 AIE-ML array):")
print("   ", result.summary())
print(f"    -> {result.latency_ns / 1e3:.2f} us vs the 1 us budget; "
      f"{result.cascade_edges} cascade edges")

# --- 2. the fused cascade kernel ----------------------------------------------
from repro.launch import platform
from repro.quant import quantize_mlp
from repro.kernels.cascade_mlp import cascade_mlp, cascade_mlp_ref

rng = np.random.default_rng(0)
sizes = [16, 64, 32, 5]
ws = [rng.normal(0, 0.3, (sizes[i], sizes[i + 1])) for i in range(3)]
bs = [rng.normal(0, 0.1, n) for n in sizes[1:]]
x = rng.normal(0, 1, (64, 16)).astype(np.float32)
qmlp = quantize_mlp(ws, bs, [True, True, False], x)
xq = jnp.clip(jnp.round(jnp.asarray(x) / 2.0 ** qmlp.e_in),
              -128, 127).astype(jnp.int8)
out = cascade_mlp(xq, qmlp, interpret=platform.interpret())
ref = cascade_mlp_ref(xq, qmlp)
print(f"[2] fused cascade kernel == oracle: {bool(jnp.all(out == ref))} "
      f"(INT8, bit-exact)")

# --- 3. an assigned architecture ----------------------------------------------
from repro import optim
from repro.configs import get_reduced
from repro.distributed import steps
from repro.models import build

cfg = get_reduced("qwen3-14b")
m = build(cfg)
params = m.init(jax.random.key(0))
tstep = jax.jit(steps.make_train_step(cfg, optim.AdamWConfig(lr=1e-3)))
batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
         "labels": jnp.zeros((2, 16), jnp.int32)}
params2, _, metrics = tstep(params, optim.init(params), batch)
cache = m.init_cache(batch=2, max_len=32)
logits, cache = jax.jit(m.decode_step)(params2,
                                       jnp.zeros((2, 1), jnp.int32), cache)
print(f"[3] {cfg.name}: train loss {float(metrics['loss']):.3f}, "
      f"decode logits {logits.shape} — substrate OK")
