"""Particle Transformer (ParT) for jet tagging, served one jet at a time.

Qu, Li and Qian, "Particle Transformer for Jet Tagging", ICML 2022,
arXiv:2202.03772; reference code weaver-core
``weaver/nn/model/ParticleTransformer.py`` at its JetClass defaults
(``embed_dims=[128, 512, 128]``, ``pair_embed_dims=[64, 64, 64]``, 8 heads,
8 particle blocks, 2 class blocks, ``fc_params=[]``, GELU,
``use_pre_activation_pair=True``, every block scale on, dropout off).

An event is one jet, float32 ``(N, 22)``: 17 particle features, then px,
py, pz, E, then a validity mask; padded slots are zero with mask 0. The
answer is ``(1, num_classes)`` float32 logits (no softmax).

The forward pass, in weaver's names:

  * ``part.embed``: BN(17), then [LN, Linear, GELU] to 128, 512, 128
    (``Embed``); padded rows set to zero.
  * ``part.pair_features``: for every pair, weaver's ``pairwise_lv_fts``
    with four outputs, in its order [ln kT, ln z, ln Delta, ln m^2], with
    rapidity, Delta-phi wrapped by ``(a - b + pi) mod 2 pi - pi`` and every
    clamp at eps = 1e-8.
  * ``part.pair_embed``: BN(4), then 1x1 convolutions 4 -> 64 -> 64 -> 64
    -> 8, each followed by BN and, except the last, GELU (``PairEmbed``,
    pre-activation); computed over the pairs i >= j, self-pairs included,
    and made symmetric: U (heads, N, N).
  * ``part.block{i}``, 8 of them (NormFormer ``Block``):
    x += LN(c_attn-scaled MHA(LN(x))), with scores q k^T / sqrt(16) + U_h
    and padded keys left out; then x = fc2(LN(GELU(fc1(LN(x))))) +
    w_resid * x. ``c_attn`` scales each head of the projected output viewed
    as (heads, 16), and weaver's ``einsum('tbhd,h->tbdh')`` then reshapes
    it as (16, heads): that permutation is kept.
  * ``part.cls_block{i}``, 2 of them: the learned class token c is the
    query (not layer-normed, as in weaver) over keys and values
    LN([c; x]), with c never masked and no pair bias; the rest as above.
  * ``part.head``: Linear(LN(c)) to the class logits.

:func:`part_forward` is the served function: matrix-product inputs in
bfloat16 with float32 accumulation, everything else (LayerNorm, BatchNorm
folded to a scale and shift, exact-erf GELU, softmax, pair features) in
float32, and the particle blocks' attention in ``kernels/flash_attn``. The
pair embedding runs over the N(N+1)/2 pairs i >= j laid out as an
(N + 1) x N/2 rectangle (:func:`_pack`), so no gather or scatter is needed
to build or unfold it. :func:`part_ref` is the plain reference.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attn import flash_mha_bias
from repro.launch import platform

#: BatchNorm's and LayerNorm's epsilon (torch's default), and the clamp of
#: weaver's pair features.
NORM_EPS = 1e-5
PAIR_EPS = 1e-8
N_FEATURES = 17
#: Columns of an event: the features, px, py, pz, E, the mask.
N_COLUMNS = N_FEATURES + 5
NEG_INF = -1e30

#: ``mm(subscripts, a, b)``: one matrix product, float32 out.
MatMul = Callable[[str, jax.Array, jax.Array], jax.Array]


def _mm_served(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _mm_ref(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _dense(mm: MatMul, x: jax.Array, p: dict) -> jax.Array:
    return mm("...i,io->...o", x, p["w"]) + p["b"]


def _ln(x: jax.Array, p: dict) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + NORM_EPS) * p["gamma"] + p["beta"]


def _bn(x: jax.Array, p: dict) -> jax.Array:
    """BatchNorm in eval mode over the last axis, folded."""
    scale = p["gamma"] * jax.lax.rsqrt(p["var"] + NORM_EPS)
    return x * scale + (p["beta"] - p["mean"] * scale)


def _gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=False)


def _square(a: jax.Array):
    """a * a as hi + lo exactly (Dekker's product, a split at 12 bits)."""
    c = 4097.0 * a
    hi = c - (c - a)
    lo = a - hi
    p = a * a
    return p, ((hi * hi - p) + 2.0 * hi * lo) + lo * lo


def _mass2(v: jax.Array) -> jax.Array:
    """E^2 - |p|^2 of (N, 4) four-vectors, summed with every rounding
    error carried (TwoSum), so it is exact to float32 rounding of the
    result: the difference of squares itself loses a light particle's
    mass to rounding at E ~ 10^2 GeV."""
    terms = [_square(v[:, k]) for k in (3, 0, 1, 2)]
    total, carry = terms[0]
    for p, lo in terms[1:]:
        s = total - p
        back = s - total
        carry = carry + ((total - (s - back)) - (p + back)) - lo
        total = s
    return total + carry


def pair_features(v: jax.Array) -> jax.Array:
    """(N, 4) four-vectors (px, py, pz, E) -> (4, N, N): weaver's
    ``pairwise_lv_fts(x_i, x_j)`` [ln kT, ln z, ln Delta, ln m^2] for every
    ordered pair (i, j).

    m^2 = (E_i + E_j)^2 - |p_i + p_j|^2 is evaluated as m_i^2 + m_j^2 +
    2 (d_i E_j + |p_i| d_j + |p_i| |p_j| |n_i - n_j|^2 / 2), with
    d = E - |p| = m^2 / (E + |p|) and n = p / |p|: the same number, but a sum
    of terms that do not cancel, each particle's m^2 from :func:`_mass2`.
    Weaver's difference of squares in float32 leaves the m^2 of a
    self-pair or a near-collinear pair of light particles to rounding,
    which two correct implementations then round differently.
    """
    px, py, pz, e = (v[:, k] for k in range(4))
    pt = jnp.sqrt(px * px + py * py)
    rap = 0.5 * jnp.log(1 + 2 * pz / jnp.maximum(e - pz, 1e-20))
    phi = jnp.arctan2(py, px)
    col, row = (lambda a: a[:, None]), (lambda a: a[None, :])
    dphi = jnp.mod(col(phi) - row(phi) + math.pi, 2 * math.pi) - math.pi
    delta = jnp.sqrt(jnp.square(col(rap) - row(rap)) + jnp.square(dphi))
    ptmin = jnp.minimum(col(pt), row(pt))
    lnkt = jnp.log(jnp.maximum(ptmin * delta, PAIR_EPS))
    lnz = jnp.log(jnp.maximum(
        ptmin / jnp.maximum(col(pt) + row(pt), PAIR_EPS), PAIR_EPS))
    lndelta = jnp.log(jnp.maximum(delta, PAIR_EPS))
    m2 = _mass2(v)
    p = jnp.sqrt(px * px + py * py + pz * pz)
    d = m2 / jnp.maximum(e + p, PAIR_EPS)
    n = v[:, :3] / jnp.maximum(p, PAIR_EPS)[:, None]
    gap = jnp.sum(jnp.square(n[:, None, :] - n[None, :, :]), axis=-1)
    m2 = (col(m2) + row(m2) + 2 * (col(d) * row(e) + col(p) * row(d))
          + col(p) * row(p) * gap)
    lnm2 = jnp.log(jnp.maximum(m2, PAIR_EPS))
    return jnp.stack([lnkt, lnz, lndelta, lnm2])


def _pack(f: jax.Array) -> jax.Array:
    """(..., N, N) -> (..., N + 1, N/2): each pair i >= j once, N even.

    With halves a and b of the particles, rows 0..N/2 hold the lower
    triangle of the (a, a) block strictly below the diagonal of the
    rectangle, shifted down one row, and the transposed lower triangle of
    (b, b) on and above it; rows N/2 + 1.. hold the whole (b, a) block.
    """
    h = f.shape[-1] // 2
    aa, bb, ba = f[..., :h, :h], f[..., h:, h:], f[..., h:, :h]
    zero = jnp.zeros_like(aa[..., :1, :])
    below = jnp.concatenate([zero, aa], axis=-2)     # [r, c] = aa[r - 1, c]
    above = jnp.concatenate([jnp.swapaxes(bb, -1, -2), zero], axis=-2)
    r, c = np.arange(h + 1)[:, None], np.arange(h)[None, :]
    return jnp.concatenate([jnp.where(c < r, below, above), ba], axis=-2)


def _unpack(g: jax.Array) -> jax.Array:
    """(..., N + 1, N/2) from :func:`_pack` -> the symmetric (..., N, N)."""
    h = g.shape[-1]
    t = lambda a: jnp.swapaxes(a, -1, -2)
    low, up, ba = g[..., 1:h + 1, :], g[..., :h, :], g[..., h + 1:, :]
    r, c = np.arange(h)[:, None], np.arange(h)[None, :]
    aa = jnp.where(c <= r, low, t(low))
    bb = jnp.where(c <= r, t(up), up)
    return jnp.concatenate([jnp.concatenate([aa, t(ba)], axis=-1),
                            jnp.concatenate([ba, bb], axis=-1)], axis=-2)


def _pair_embed(mm: MatMul, p: dict, f: jax.Array) -> jax.Array:
    """(..., 4) pair features -> (..., heads)."""
    y = _bn(f, p["bn"])
    for i, layer in enumerate(p["layers"]):
        y = _bn(_dense(mm, y, layer), layer["bn"])
        if i < len(p["layers"]) - 1:
            y = _gelu(y)
    return y


def _attend(mm: MatMul, q, k, v, bias, valid) -> jax.Array:
    """Softmax attention per head in plain jnp. q (H, S, d), k/v (H, T, d),
    bias (H, S, T) or None, valid (T,) -> (H, S, d)."""
    s = mm("hsd,htd->hst", q, k) / math.sqrt(q.shape[-1])
    if bias is not None:
        s = s + bias
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    return mm("hst,htd->hsd", jax.nn.softmax(s, axis=-1), v)


def _attend_kernel(q, k, v, bias, valid) -> jax.Array:
    bf16 = lambda a: a.astype(jnp.bfloat16)[None]
    return flash_mha_bias(bf16(q), bf16(k), bf16(v), bias[None],
                          valid[None], interpret=platform.interpret())[0]


def _block(mm: MatMul, attend, p: dict, x: jax.Array, bias, valid,
           cls=None) -> jax.Array:
    """One particle block (``cls`` None) or class block; returns the new
    x, or the new class token."""
    c = x.shape[-1]
    heads = p["head_scale"].shape[0]
    split = lambda a: a.reshape(a.shape[0], heads, -1).swapaxes(0, 1)
    w, b = p["qkv"]["w"], p["qkv"]["b"]
    if cls is None:
        residual = x
        y = _ln(x, p["ln_attn"])
        q, k, v = (split(t) for t in jnp.split(_dense(
            mm, y, p["qkv"]), 3, axis=-1))
        o = attend(q, k, v, bias, valid)
    else:
        residual = cls
        y = _ln(jnp.concatenate([cls, x]), p["ln_attn"])
        q = split(mm("...i,io->...o", cls, w[:, :c]) + b[:c])
        k, v = (split(t) for t in jnp.split(
            mm("...i,io->...o", y, w[:, c:]) + b[c:], 2, axis=-1))
        o = _attend(mm, q, k, v, None,
                    jnp.concatenate([jnp.ones((1,), bool), valid]))
    o = _dense(mm, o.swapaxes(0, 1).reshape(-1, c), p["out"])
    # weaver: x.view(t, b, h, d); einsum('tbhd,h->tbdh', x, c_attn); reshape
    o = (o.reshape(-1, heads, c // heads) * p["head_scale"][:, None])
    o = o.swapaxes(1, 2).reshape(-1, c)
    x = residual + _ln(o, p["ln_post_attn"])
    y = _gelu(_dense(mm, _ln(x, p["ln_fc"]), p["fc1"]))
    y = _dense(mm, _ln(y, p["ln_post_fc"]), p["fc2"])
    return y + p["resid_scale"] * x


def _forward(params: dict, event: jax.Array, mm: MatMul, attend,
             pair_bias) -> jax.Array:
    feats, v = event[:, :N_FEATURES], event[:, N_FEATURES:N_FEATURES + 4]
    mask = event[:, N_FEATURES + 4]
    valid = mask > 0
    with jax.named_scope("part.embed"):
        x = _bn(feats, params["embed"]["bn"])
        for layer in params["embed"]["layers"]:
            x = _gelu(_dense(mm, _ln(x, layer["ln"]), layer))
        x = x * mask[:, None]
    with jax.named_scope("part.pair_features"):
        f = pair_features(v)
    with jax.named_scope("part.pair_embed"):
        u = pair_bias(mm, params["pair_embed"], f)
    for i, p in enumerate(params["blocks"]):
        with jax.named_scope(f"part.block{i}"):
            x = _block(mm, attend, p, x, u, valid)
    cls = params["cls_token"][None, :]
    for i, p in enumerate(params["cls_blocks"]):
        with jax.named_scope(f"part.cls_block{i}"):
            cls = _block(mm, attend, p, x, None, valid, cls=cls)
    with jax.named_scope("part.head"):
        return _dense(mm, _ln(cls, params["norm"]), params["fc"])


def _pair_bias_packed(mm: MatMul, p: dict, f: jax.Array) -> jax.Array:
    """U over the pairs i >= j only, packed, embedded, unfolded."""
    e = _pair_embed(mm, p, jnp.moveaxis(_pack(f), 0, -1))
    return _unpack(jnp.moveaxis(e, -1, 0))


def _pair_bias_full(mm: MatMul, p: dict, f: jax.Array) -> jax.Array:
    """U over every ordered pair, then the lower triangle mirrored."""
    e = jnp.moveaxis(_pair_embed(mm, p, jnp.moveaxis(f, 0, -1)), -1, 0)
    n = e.shape[-1]
    lower = np.arange(n)[:, None] >= np.arange(n)[None, :]
    return jnp.where(lower, e, jnp.swapaxes(e, -1, -2))


def part_forward(params: dict, event: jax.Array) -> jax.Array:
    """The served ParT: one jet (N, 22) float32 -> (1, classes) float32."""
    return _forward(params, event, _mm_served, _attend_kernel,
                    _pair_bias_packed)


def part_ref(params: dict, event: jax.Array) -> jax.Array:
    """The plain reference: jax.numpy in float32 at full matmul precision,
    no kernel, the pair embedding over all N^2 pairs with the lower
    triangle mirrored.

    Departures from weaver, all exact in eval mode: BatchNorm folded to a
    scale and shift; padded slots kept (masked) where weaver's trimmer cuts
    the batch to its longest jet; the pair features of every pair
    computed, weaver's kept for i >= j only; m^2 of a pair summed without
    cancellation (:func:`pair_features`); a fully masked key set averages
    V instead of giving NaN (no real jet has one).
    """
    with jax.default_matmul_precision("highest"):
        return _forward(params, event.astype(jnp.float32), _mm_ref,
                        lambda *a: _attend(_mm_ref, *a), _pair_bias_full)


def serving_params(params: dict) -> dict:
    """The served copy of ``params``: every matrix-product weight ("w") in
    bfloat16, the rest float32."""
    def cast(path, a):
        last = path[-1]
        dtype = (jnp.bfloat16 if getattr(last, "key", None) == "w"
                 else jnp.float32)
        return jnp.asarray(a, dtype)
    return jax.tree_util.tree_map_with_path(cast, params)


def _norm(n: int) -> dict:
    return {"gamma": np.ones(n, np.float32), "beta": np.zeros(n, np.float32)}


def _batchnorm(n: int) -> dict:
    return dict(_norm(n), mean=np.zeros(n, np.float32),
                var=np.ones(n, np.float32))


def _linear(rng: np.random.Generator, n_in: int, n_out: int) -> dict:
    """torch's nn.Linear / 1x1 Conv1d default: U(+-1/sqrt(n_in))."""
    bound = 1.0 / math.sqrt(n_in)
    return {"w": rng.uniform(-bound, bound, (n_in, n_out)).astype(np.float32),
            "b": rng.uniform(-bound, bound, n_out).astype(np.float32)}


def _block_params(rng: np.random.Generator, c: int, heads: int,
                  ffn: int) -> dict:
    # nn.MultiheadAttention: xavier-uniform in-projection, zero biases.
    bound = math.sqrt(6.0 / (c + 3 * c))
    out = _linear(rng, c, c)
    out["b"][:] = 0.0
    return {"ln_attn": _norm(c),
            "qkv": {"w": rng.uniform(-bound, bound, (c, 3 * c)
                                     ).astype(np.float32),
                    "b": np.zeros(3 * c, np.float32)},
            "out": out, "head_scale": np.ones(heads, np.float32),
            "ln_post_attn": _norm(c), "ln_fc": _norm(c),
            "fc1": _linear(rng, c, ffn), "ln_post_fc": _norm(ffn),
            "fc2": _linear(rng, ffn, c),
            "resid_scale": np.ones(c, np.float32)}


def init_params(seed: int, *, input_dim: int = N_FEATURES,
                num_classes: int = 10, embed_dims=(128, 512, 128),
                pair_embed_dims=(64, 64, 64), num_heads: int = 8,
                num_layers: int = 8, num_cls_layers: int = 2,
                ffn_ratio: int = 4) -> dict:
    """Float32 parameters with torch's default initialisation (weaver's
    defaults as keyword arguments); BatchNorm at mean 0, variance 1."""
    rng = np.random.default_rng(seed)
    embed, d = [], input_dim
    for n in embed_dims:
        embed.append(dict(_linear(rng, d, n), ln=_norm(d)))
        d = n
    pair, d = [], 4
    for n in list(pair_embed_dims) + [num_heads]:
        pair.append(dict(_linear(rng, d, n), bn=_batchnorm(n)))
        d = n
    c = embed_dims[-1]
    block = lambda: _block_params(rng, c, num_heads, ffn_ratio * c)
    # trunc_normal_(std=.02) truncates at +-2 absolute: a plain normal here.
    token = rng.normal(0, 0.02, c).astype(np.float32)
    return {"embed": {"bn": _batchnorm(input_dim), "layers": embed},
            "pair_embed": {"bn": _batchnorm(4), "layers": pair},
            "blocks": [block() for _ in range(num_layers)],
            "cls_blocks": [block() for _ in range(num_cls_layers)],
            "cls_token": token, "norm": _norm(c),
            "fc": _linear(rng, c, num_classes)}
