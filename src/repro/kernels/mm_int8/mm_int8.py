"""Blocked INT8 matmul Pallas kernel with fused bias/ReLU/requant epilogue.

TPU adaptation of the paper's single-AIE MM kernel (§4.1):

* The AIE VMAC block B_M x B_K x B_N (4x8x8 INT8) becomes an MXU-aligned
  VMEM tile: the MXU is a 128x128 systolic array, so block shapes are
  multiples of (8 sublanes, 128 lanes) with K kept whole per tile (the
  paper's output-stationary j-loop maps to the K-contraction inside one
  ``jnp.dot``; XLA pipelines HBM->VMEM loads across grid steps, which is
  the analogue of the II=1 load-compute pipeline).
* The paper's fused bias+ReLU epilogue on the rightmost AIE column (§4.3.2)
  becomes the in-kernel epilogue: bias add in INT32, ReLU, and the
  power-of-two requantization shift (AIE SRS instruction ~ shift+saturate).

The kernel assumes shapes pre-padded to the block grid (``ops.py`` pads).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant import INT8_MAX, INT8_MIN

# MXU-aligned default tile (int8: 32-sublane packing; lanes = 128).
DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128


def _epilogue(acc: jnp.ndarray, bias_blk: Optional[jnp.ndarray], *,
              relu: bool, shift: int, out_int8: bool) -> jnp.ndarray:
    if bias_blk is not None:
        acc = acc + bias_blk.astype(jnp.int32)
    if relu:
        acc = jnp.maximum(acc, 0)
    if not out_int8:
        return acc
    if shift > 0:
        rnd = jnp.where(acc >= 0, 1 << (shift - 1), (1 << (shift - 1)) - 1)
        acc = (acc + rnd) >> shift
    return jnp.clip(acc, INT8_MIN, INT8_MAX).astype(jnp.int8)


def _kernel_nobias(x_ref, w_ref, o_ref, *, relu, shift, out_int8):
    acc = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.int32)
    o_ref[...] = _epilogue(acc, None, relu=relu, shift=shift,
                           out_int8=out_int8)


def _kernel_bias(x_ref, w_ref, b_ref, o_ref, *, relu, shift, out_int8):
    acc = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.int32)
    o_ref[...] = _epilogue(acc, b_ref[...], relu=relu, shift=shift,
                           out_int8=out_int8)


def mm_int8_pallas(x: jax.Array, w: jax.Array,
                   bias: Optional[jax.Array] = None, *,
                   shift: int = 0, relu: bool = False, out_int8: bool = True,
                   block_m: int = DEFAULT_BLOCK_M,
                   block_n: int = DEFAULT_BLOCK_N,
                   interpret: bool = False) -> jax.Array:
    """Blocked INT8 MM. x: (M, K) int8, w: (K, N) int8, bias: (1, N) int32.

    Grid is (M/block_m, N/block_n); each program reads an (block_m, K)
    stripe of x and a (K, block_n) stripe of w — the K contraction runs
    whole inside the MXU dot, keeping the output stationary (paper §4.1).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    assert M % block_m == 0 and N % block_n == 0, "ops.py must pad"

    grid = (M // block_m, N // block_n)
    out_dtype = jnp.int8 if out_int8 else jnp.int32
    in_specs = [
        pl.BlockSpec((block_m, K), lambda i, j: (i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((K, block_n), lambda i, j: (0, j),
                     memory_space=pltpu.VMEM),
    ]
    if bias is not None:
        assert bias.shape == (1, N) and bias.dtype == jnp.int32
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j: (0, j),
                                     memory_space=pltpu.VMEM))
        kernel = functools.partial(_kernel_bias, relu=relu, shift=shift,
                                   out_int8=out_int8)
        args = (x, w, bias)
    else:
        kernel = functools.partial(_kernel_nobias, relu=relu, shift=shift,
                                   out_int8=out_int8)
        args = (x, w)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        name="mm_int8",
        interpret=interpret,
    )(*args)
