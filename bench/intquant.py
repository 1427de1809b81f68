"""The benchmark's own INT8 weights and plain integer reference.

The served tagger computes, per layer (paper section 4.3.2):

    acc = x_int8 @ w_int8 + b_int32          (int32 accumulator)
    acc = max(acc, 0)                        (where the layer has a ReLU)
    y   = clip((acc + rnd) >> shift, -128, 127)

with ``rnd`` rounding half away from zero: ``2**(shift-1)`` for ``acc >= 0``
and ``2**(shift-1) - 1`` below. Scales are powers of two, so requantizing is
the right shift. This module writes that down in numpy, imports nothing of
the program, and makes the weights itself from the seed: a post-training
quantization that copies the program's power-of-two scheme (percentile
calibration of activations, max of weights).

``int4=True`` gives the control: the same network with weights and
activations rounded to the int4 grid (multiples of 16 in int8 units), the
nearest precision below the int8 that the configurations state.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

INT8_MIN, INT8_MAX = -128, 127
ACT_PERCENTILE = 99.5


@dataclasses.dataclass(frozen=True)
class Layer:
    w: np.ndarray        # (K, N) int8
    b: np.ndarray        # (N,) int32, at the accumulator's scale
    shift: int           # >= 0
    relu: bool
    e_w: int
    e_out: int


def pow2_exponent(x: np.ndarray, percentile: float = 100.0) -> int:
    """Smallest e with |x| (at ``percentile``) / 2**e <= 127."""
    a = np.abs(np.asarray(x, np.float64))
    amax = float(np.percentile(a, percentile) if percentile < 100.0
                 else a.max()) or 1e-8
    return int(np.ceil(np.log2(max(amax, 1e-8) / INT8_MAX)))


def quantize(x: np.ndarray, e: int) -> np.ndarray:
    return np.clip(np.round(np.asarray(x) / 2.0 ** e),
                   INT8_MIN, INT8_MAX).astype(np.int8)


def quantize_mlp(ws: Sequence[np.ndarray], bs: Sequence[np.ndarray],
                 relus: Sequence[bool], sample: np.ndarray,
                 e_in: Optional[int] = None):
    """(e_in, layers) for a float MLP calibrated on ``sample`` rows; ``e_in``
    is the scale of the int8 input where an earlier stage fixed it."""
    if e_in is None:
        e_in = pow2_exponent(sample, ACT_PERCENTILE)
    x, e_prev, layers = np.asarray(sample, np.float64), e_in, []
    for w, b, relu in zip(ws, bs, relus):
        y = x @ w + b
        if relu:
            y = np.maximum(y, 0.0)
        e_out = pow2_exponent(y, ACT_PERCENTILE)
        e_w = pow2_exponent(w)
        acc_e = e_prev + e_w
        shift = max(0, e_out - acc_e)
        layers.append(Layer(w=quantize(w, e_w),
                            b=np.round(b / 2.0 ** acc_e).astype(np.int32),
                            shift=shift, relu=relu, e_w=e_w,
                            e_out=acc_e + shift))
        x, e_prev = y, acc_e + shift
    return e_in, layers


def float_mlp(rng: np.random.Generator, k: int, nodes: Sequence[int]):
    """He-initialised weights with small random biases (nonzero, so that the
    check covers the bias add)."""
    ws, bs = [], []
    for n in nodes:
        ws.append(rng.normal(0.0, np.sqrt(2.0 / k), (k, n)))
        bs.append(rng.normal(0.0, 0.1, n))
        k = n
    return ws, bs


def requant(acc: np.ndarray, shift: int) -> np.ndarray:
    acc = acc.astype(np.int64)
    if shift > 0:
        rnd = np.where(acc >= 0, 1 << (shift - 1), (1 << (shift - 1)) - 1)
        acc = (acc + rnd) >> shift
    return np.clip(acc, INT8_MIN, INT8_MAX).astype(np.int8)


def _int4(a: np.ndarray) -> np.ndarray:
    return (np.clip(np.round(a.astype(np.float64) / 16.0), -8, 7)
            * 16).astype(np.int64)


def mlp(x: np.ndarray, layers: List[Layer], *, int4: bool = False
        ) -> np.ndarray:
    """Rows of int8 ``x`` (..., K0) through the layer chain -> int8."""
    a = x
    for l in layers:
        w = _int4(l.w) if int4 else l.w
        if int4:
            a = _int4(a)
        # int8 x int8 sums over K <= 128 stay far below 2**53, so a float64
        # product is exact and much faster than an integer one in numpy.
        acc = np.rint(a.astype(np.float64) @ w.astype(np.float64)
                      ).astype(np.int64) + l.b.astype(np.int64)
        if l.relu:
            acc = np.maximum(acc, 0)
        a = requant(acc, l.shift)
    return a


def mean_over_set(h: np.ndarray) -> np.ndarray:
    """Mean over axis -2 of a power-of-two set: int32 sum, then the shift."""
    m = h.shape[-2]
    assert m & (m - 1) == 0, "the set size must be a power of two"
    return requant(h.astype(np.int64).sum(axis=-2, keepdims=True),
                   m.bit_length() - 1)
