"""Global aggregation Pallas kernels (paper §4.3.1, Table 4).

Two implementations, mirroring the paper's comparison:

* **MAC-based (ours)** — the reduction over the set dimension is expressed
  as a matmul with a constant ones row: ``(1, M) @ (M, F)``. On AIE this
  turns many VMOV/VADD vector moves into a single VMAC; on TPU it moves the
  reduction from the VPU (vector unit) onto the **MXU** systolic array —
  the same insight transfers directly.
* **extract/add baseline** — row-by-row reads + vector add, the
  paper's in-house baseline built from extract()/aie::add/insert(). On TPU
  this lowers to a serial chain of VPU adds with relayouts.

`benchmarks/table4_global_agg.py` compares both against the analytical model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant import INT8_MAX, INT8_MIN

DEFAULT_BLOCK_F = 128


def _requant(acc, shift):
    if shift > 0:
        rnd = jnp.where(acc >= 0, 1 << (shift - 1), (1 << (shift - 1)) - 1)
        acc = (acc + rnd) >> shift
        return jnp.clip(acc, INT8_MIN, INT8_MAX).astype(jnp.int8)
    return acc


def _mac_kernel(x_ref, o_ref, *, shift: int):
    M = x_ref.shape[0]
    ones = jnp.ones((1, M), jnp.int8)           # constant LHS (paper Fig. 7)
    acc = jnp.dot(ones, x_ref[...], preferred_element_type=jnp.int32)
    o_ref[...] = _requant(acc, shift)


def _extract_add_kernel(x_ref, o_ref, *, shift: int):
    # Rows are read from the ref at static offsets: Mosaic lowers no
    # dynamic_slice of a loaded value, and refuses a dynamic one-row ref
    # read of int8 whose offset it cannot prove 8-row aligned.
    acc = jnp.zeros((1, x_ref.shape[1]), jnp.int32)
    for i in range(x_ref.shape[0]):
        acc = acc + x_ref[i:i + 1, :].astype(jnp.int32)
    o_ref[...] = _requant(acc, shift)


def global_agg_pallas(x: jax.Array, *, op: str = "sum",
                      impl: str = "mac",
                      block_f: int = DEFAULT_BLOCK_F,
                      interpret: bool = False) -> jax.Array:
    """Reduce (M, F) int8 over M. F must be a multiple of block_f (pre-pad).

    op: 'sum' -> int32 out; 'mean' -> int8 out via shift (M power of two).
    impl: 'mac' (MXU ones-matmul) or 'extract_add' (VPU row-adds baseline).
    """
    M, F = x.shape
    assert F % block_f == 0
    shift = 0
    out_dtype = jnp.int32
    if op == "mean":
        assert M & (M - 1) == 0
        shift = M.bit_length() - 1
        out_dtype = jnp.int8
    kernel = functools.partial(
        _mac_kernel if impl == "mac" else _extract_add_kernel, shift=shift)
    return pl.pallas_call(
        kernel,
        grid=(F // block_f,),
        in_specs=[pl.BlockSpec((M, block_f), lambda j: (0, j),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, block_f), lambda j: (0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, F), out_dtype),
        name="global_agg",
        interpret=interpret,
    )(x)
