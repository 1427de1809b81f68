"""Jitted GQA-aware wrapper for the flash attention kernel.

Accepts the model-layout tensors (B, S, H, hd) / (B, T, KV, hd), repeats KV
groups, collapses batch x heads, pads sequence lengths to the block grid,
and slices back. Padded key rows are masked by construction for the causal
case (pad queries attend only to themselves; their output rows are sliced
off). The non-causal entry, ``flash_mha_bias``, takes head-major tensors,
an additive bias and a key mask (the Particle Transformer's attention);
its caller pads, and masks the padded keys through that mask.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attn import flash_attention


def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
              block_q: int = 128, block_k: int = 128,
              interpret: bool = False) -> jax.Array:
    """Causal GQA flash attention. q (B, S, H, hd); k/v (B, T, KV, hd) with
    T == S (self-attention). Returns (B, S, H*hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    n_rep = H // KV
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    # (B, S, H, hd) -> (B*H, S, hd)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    Sp = _round_up(S, max(block_q, block_k))
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0))
        qf, kf, vf = (jnp.pad(t, pad) for t in (qf, kf, vf))
    out = flash_attention(qf, kf, vf, causal=True, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    out = out[:, :S]
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3).reshape(B, S,
                                                                  H * hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_mha_bias(q: jax.Array, k: jax.Array, v: jax.Array,
                   bias: jax.Array, kv_mask: jax.Array, *,
                   interpret: bool = False) -> jax.Array:
    """Non-causal flash attention with a per-head additive bias and a key
    mask. q/k/v (B, H, S, hd), bias (B, H, S, S) added after the 1/sqrt(hd)
    scaling, kv_mask (B, S) (nonzero = a real key). Returns (B, H, S, hd).

    One grid step holds all H heads of a sequence: up to 128 positions the
    block is the whole (S x S) tile, beyond that S is a multiple of 128 and
    the blocks are 128 x 128. Padded positions are the caller's, masked
    through ``kv_mask``.
    """
    B, H, S, hd = q.shape
    block = min(S, 128)
    out = flash_attention(
        *(t.reshape(B * H, S, hd) for t in (q, k, v)), causal=False,
        block_q=block, block_k=block, block_b=H,
        bias=bias.reshape(B * H, S, S),
        kv_mask=jnp.broadcast_to(kv_mask[:, None, :], (B, H, S)).reshape(
            B * H, S),
        interpret=interpret)
    return out.reshape(B, H, S, hd)
