"""Pure-jnp oracle for the flash attention kernel."""
from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True,
                        scale: Optional[float] = None,
                        bias: Optional[jnp.ndarray] = None,
                        kv_mask: Optional[jnp.ndarray] = None
                        ) -> jnp.ndarray:
    """q (BH, S, d), k/v (BH, T, d) -> (BH, S, d); f32 softmax. ``bias``
    (BH, S, T) is added after the scaling; keys where ``kv_mask`` (BH, T) is
    zero score -1e30, as in the kernel."""
    BH, S, d = q.shape
    T = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    s = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, :] != 0, s, NEG_INF)
    if causal:
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(T)[None, :]
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.einsum("bst,btd->bsd", w, v.astype(jnp.float32)
                      ).astype(q.dtype)
