"""Fused multi-layer MLP Pallas kernel — the TPU cascade analogue (core idea).

The paper's headline mechanism: all layers live on-chip simultaneously and
intermediate activations never leave the fast fabric (512-bit cascade FIFOs
between AIE tiles). On TPU the analogous fast path is *VMEM residency*: one
``pallas_call`` executes the entire MLP, weights are pinned in VMEM for the
kernel's lifetime, and inter-layer activations are register/VMEM values that
never round-trip through HBM.

Contrast with the per-layer baseline (``kernels/mm_int8`` chained): L kernel
launches, and every intermediate activation is written to and re-read from
HBM — the 32-bit/cycle-DMA analogue. ``benchmarks/tpu_cascade_fusion.py``
quantifies the HBM-bytes and launch-count reduction.

Layout constraint (mirrors the paper's cascade legality rule): a chain can be
fused only when its total VMEM working set fits the budget — checked by
``repro.core.fusion_planner`` exactly like the A=A', C=C'=1 rule gates the
AIE cascade.

The grid runs over M blocks (the set/batch dimension): each program carries
its activation stripe through every layer. This is the same loop structure
as Fig. 6's receiver: "save the data corresponding to its location, then
load from local memory, compute, store" — with XLA/Mosaic pipelining the
next grid step's input DMA under the current step's compute, the analogue of
cascade's producer/consumer overlap.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant import INT8_MAX, INT8_MIN, QuantizedMLP

DEFAULT_BLOCK_M = 128


def _requant(acc, shift):
    if shift > 0:
        rnd = jnp.where(acc >= 0, 1 << (shift - 1), (1 << (shift - 1)) - 1)
        acc = (acc + rnd) >> shift
    return jnp.clip(acc, INT8_MIN, INT8_MAX).astype(jnp.int8)


def _mlp_body(a, w_refs, b_refs, shifts, relus):
    """Run the fused layer chain on activation value ``a`` (int8)."""
    for w_ref, b_ref, shift, relu in zip(w_refs, b_refs, shifts, relus):
        acc = jnp.dot(a, w_ref[...], preferred_element_type=jnp.int32)
        if b_ref is not None:
            acc = acc + b_ref[...].astype(jnp.int32)
        if relu:
            acc = jnp.maximum(acc, 0)
        a = _requant(acc, shift)
    return a


def _make_kernel(n_layers: int, has_bias: Tuple[bool, ...],
                 shifts: Tuple[int, ...], relus: Tuple[bool, ...]):
    def kernel(x_ref, *refs):
        o_ref = refs[-1]
        w_refs, b_refs = [], []
        it = iter(refs[:-1])
        for i in range(n_layers):
            w_refs.append(next(it))
            b_refs.append(next(it) if has_bias[i] else None)
        o_ref[...] = _mlp_body(x_ref[...], w_refs, b_refs, shifts, relus)
    return kernel


def cascade_mlp_pallas(x: jax.Array, qmlp: QuantizedMLP, *,
                       block_m: int = DEFAULT_BLOCK_M,
                       interpret: bool = False) -> jax.Array:
    """Fused INT8 MLP: one pallas_call for the whole layer chain.

    x: (M, K0) int8 pre-padded to block_m and lane-aligned feature dims.
    Weights/biases are whole-array VMEM blocks (index_map constant): they are
    loaded once and stay resident across grid steps — the "preloaded to AIE
    local memory as runtime parameters" of §4.1.
    """
    M, K0 = x.shape
    assert M % block_m == 0
    n_layers = len(qmlp.layers)
    has_bias = tuple(l.bias_q is not None for l in qmlp.layers)
    shifts = tuple(l.shift for l in qmlp.layers)
    relus = tuple(l.relu for l in qmlp.layers)
    n_out = qmlp.layers[-1].w_q.shape[1]

    args = [x]
    in_specs = [pl.BlockSpec((block_m, K0), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)]
    for l in qmlp.layers:
        k, n = l.w_q.shape
        args.append(l.w_q)
        in_specs.append(pl.BlockSpec((k, n), lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        if l.bias_q is not None:
            args.append(l.bias_q.reshape(1, n))
            in_specs.append(pl.BlockSpec((1, n), lambda i: (0, 0),
                                         memory_space=pltpu.VMEM))

    kernel = _make_kernel(n_layers, has_bias, shifts, relus)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, n_out), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((M, n_out), jnp.int8),
        name="cascade_mlp",
        interpret=interpret,
    )(*args)


def deepsets_pallas(x: jax.Array, phi: QuantizedMLP, rho: QuantizedMLP, *,
                    agg: str = "mean", interpret: bool = False) -> jax.Array:
    """Fully-fused DeepSets: phi MLP -> global aggregation -> rho MLP in ONE
    pallas_call (grid=()) — the whole model on-chip, exactly the paper's
    end-to-end AIE-array execution.

    The aggregation uses the paper's MAC trick (§4.3.1): reduction over the
    set dimension is expressed as a ones-vector matmul so it runs on the MXU
    (TPU's systolic array) instead of a chain of VPU adds. x: (M, K0) int8,
    M a power of two (pre-padded).
    """
    M, K0 = x.shape
    assert M & (M - 1) == 0, "pad the set size to a power of two"
    phi_bias = tuple(l.bias_q is not None for l in phi.layers)
    rho_bias = tuple(l.bias_q is not None for l in rho.layers)
    phi_shifts = tuple(l.shift for l in phi.layers)
    rho_shifts = tuple(l.shift for l in rho.layers)
    phi_relus = tuple(l.relu for l in phi.layers)
    rho_relus = tuple(l.relu for l in rho.layers)
    # Both reductions requantize the INT32 accumulator by log2(M) before rho
    # consumes INT8; for 'mean' the shift IS the division, for 'sum' it is
    # scale management (the exponent is tracked in the quantization metadata).
    agg_shift = M.bit_length() - 1
    n_out = rho.layers[-1].w_q.shape[1]

    def pack(qmlp):
        args, specs = [], []
        for l in qmlp.layers:
            k, n = l.w_q.shape
            args.append(l.w_q)
            specs.append(pl.BlockSpec((k, n), memory_space=pltpu.VMEM))
            if l.bias_q is not None:
                args.append(l.bias_q.reshape(1, n))
                specs.append(pl.BlockSpec((1, n), memory_space=pltpu.VMEM))
        return args, specs

    phi_args, phi_specs = pack(phi)
    rho_args, rho_specs = pack(rho)
    n_phi_refs = len(phi_args)

    def kernel(x_ref, *refs):
        o_ref = refs[-1]
        refs = refs[:-1]

        def unpack(rs, qmlp, bias_flags):
            ws, bs, it = [], [], iter(rs)
            for hb in bias_flags:
                ws.append(next(it))
                bs.append(next(it) if hb else None)
            return ws, bs

        phi_w, phi_b = unpack(refs[:n_phi_refs], phi, phi_bias)
        rho_w, rho_b = unpack(refs[n_phi_refs:], rho, rho_bias)

        h = _mlp_body(x_ref[...], phi_w, phi_b, phi_shifts, phi_relus)
        # --- global aggregation as a MAC with a ones LHS (paper Fig. 7) ---
        ones = jnp.ones((1, M), jnp.int8)
        g = jnp.dot(ones, h, preferred_element_type=jnp.int32)
        g = _requant(g, agg_shift)
        o_ref[...] = _mlp_body(g, rho_w, rho_b, rho_shifts, rho_relus)

    in_specs = ([pl.BlockSpec((M, K0), memory_space=pltpu.VMEM)]
                + phi_specs + rho_specs)
    return pl.pallas_call(
        kernel,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n_out), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, n_out), jnp.int8),
        name="deepsets",
        interpret=interpret,
    )(x, *phi_args, *rho_args)
