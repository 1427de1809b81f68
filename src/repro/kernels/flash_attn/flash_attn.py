"""Blocked (flash) attention Pallas kernel for TPU.

The LM-arch serving/prefill hot spot, and the attention of the Particle
Transformer (``models/part.py``). Same scheduling idea as the paper's
cascade: the (S x T) score matrix never exists in slow memory — each
(block_q x block_k) tile lives in VMEM/VREGs, with the online-softmax
running statistics (m, l) and the output accumulator carried in VMEM
scratch across the kv grid steps (TPU grids execute sequentially, so
scratch persists along the innermost axis — the Pallas analogue of the
cascade FIFO carrying partials along the K dimension, Fig. 4d).

Grid: (B*H / block_b, n_q_blocks, n_kv_blocks), kv innermost. BlockSpecs
tile q/k/v/o into VMEM; head_dim stays whole. A grid step holds ``block_b``
rows of B*H (heads) at once, so a problem whose whole (S x T) tile is small
does not pay one grid step per head.

Optional inputs, both applied after the 1/sqrt(d) scaling, the softmax kept
in f32:

  * ``bias`` (B*H, S, T): added to the scores (ParT's pair bias U);
  * ``kv_mask`` (B*H, T): keys where it is zero get a score of -1e30. The
    sentinel is finite, so a query row with every key masked averages V
    uniformly instead of giving NaN (ParT's padded rows are sliced off later
    but must stay finite).

The tile for ParT (S = T = 128, head dim 16) is the whole problem of one
jet: ``block_q = block_k = 128`` and ``block_b`` = its 8 heads, so a batch
of jets is one grid step per jet. The bias block is then 8 x 128 x 128 f32
(512 KiB), and everything a step holds, double-buffered, stays near 5 MiB of
VMEM.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: (batch, contracting) dimensions of a scores product q k^T per head.
_QK = (((2,), (2,)), ((0,), (0,)))
#: ... and of p v.
_PV = (((2,), (1,)), ((0,), (0,)))


def _flash_kernel(*refs, scale: float, block_q: int, block_k: int,
                  causal: bool, n_kv_blocks: int, has_bias: bool,
                  has_mask: bool):
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    mask_ref = refs[3 + has_bias] if has_mask else None
    o_ref, m_scr, l_scr, acc_scr = refs[3 + has_bias + has_mask:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)                 # (bb, bq, d)
    k = k_ref[...].astype(jnp.float32)                 # (bb, bk, d)
    v = v_ref[...].astype(jnp.float32)                 # (bb, bk, d)
    s = jax.lax.dot_general(q, k, _QK,
                            preferred_element_type=jnp.float32) * scale
    if has_bias:
        s = s + bias_ref[...].astype(jnp.float32)
    if has_mask:
        s = jnp.where(mask_ref[...] != 0, s, NEG_INF)  # (bb, 1, bk) keys
    if causal:
        shape = s.shape
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_scr[...]                                # (bb, bq, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=2, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                             # (bb, bq, bk)
    corr = jnp.exp(m_prev - m_new)                     # (bb, bq, 1)
    l_new = corr * l_prev + jnp.sum(p, axis=2, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, _PV, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, block_b: int = 1,
                    scale: Optional[float] = None,
                    bias: Optional[jax.Array] = None,
                    kv_mask: Optional[jax.Array] = None,
                    interpret: bool = False) -> jax.Array:
    """q (BH, S, d), k/v (BH, T, d) -> (BH, S, d). S % block_q == 0,
    T % block_k == 0, BH % block_b == 0 (ops.py pads). ``bias`` (BH, S, T)
    is added to the scaled scores; keys where ``kv_mask`` (BH, T) is zero
    are left out."""
    BH, S, d = q.shape
    T = k.shape[1]
    assert S % block_q == 0 and T % block_k == 0 and BH % block_b == 0
    nb, nq, nk = BH // block_b, S // block_q, T // block_k
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, n_kv_blocks=nk, has_bias=bias is not None,
        has_mask=kv_mask is not None)
    in_specs = [
        pl.BlockSpec((block_b, block_q, d), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_b, block_k, d), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_b, block_k, d), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [q, k, v]
    if bias is not None:
        in_specs.append(pl.BlockSpec((block_b, block_q, block_k),
                                     lambda b, i, j: (b, i, j),
                                     memory_space=pltpu.VMEM))
        args.append(bias)
    if kv_mask is not None:
        # (BH, 1, T): a block's second-last dim must span the array's.
        in_specs.append(pl.BlockSpec((block_b, 1, block_k),
                                     lambda b, i, j: (b, 0, j),
                                     memory_space=pltpu.VMEM))
        args.append(kv_mask.astype(jnp.int32).reshape(BH, 1, T))
    return pl.pallas_call(
        kernel,
        grid=(nb, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_q, d),
                               lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_b, block_q, 1), jnp.float32),
            pltpu.VMEM((block_b, block_q, 1), jnp.float32),
            pltpu.VMEM((block_b, block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attn",
    )(*args)
