"""Particle Transformer (ParT) jet tagger, float: bf16 matrix products.

Served by the program's ``repro.serve.part_model`` (``models/part.py``,
attention in ``kernels/flash_attn``). An event is one jet padded to
``particles`` slots, float32 (particles, 22): 17 features, px, py, pz, E
and a validity mask, zero past the jet's multiplicity; the answer is
(1, classes) float32 logits.

The reference below is this file's own numpy copy of the architecture
(Qu, Li and Qian, arXiv:2202.03772; weaver-core ``ParticleTransformer``),
in float32, and imports nothing of the program. It computes each jet at its
true multiplicity, grouped by length, with no mask and no padding, so it
checks the program's masking as well as its arithmetic.
"""
from __future__ import annotations

import concurrent.futures
import math
import os
import re

import ml_dtypes
import numpy as np
import threadpoolctl
from scipy.special import erf

#: The ``repro.serve`` function that serves this kind; the fault tests wrap it.
FAULT_SITE = "part_jet"
#: Matrix products in bf16: operations are counted against the bf16 peak.
PEAK = "bf16_flops"
#: A jet's logits may differ from the reference by at most this much, each.
#: Serving rounds every matrix-product input to bf16 (a relative step of
#: 2**-8) through ten blocks: over every served jet of a chip run the largest
#: difference read 0.043, with logits of standard deviation 0.505. Float8
#: products (the control) move them by 0.149 at the median and 0.040 at the
#: least, and leaving out the pair bias U by 0.437 at the median: this limit
#: lies between, about 1.9 times the served reading and half the control's
#: median, and flags 97% of the control's jets and all but 0.01% of the
#: U-less reference's (PERF.md).
LOGIT_TOL = 0.08
NORM_EPS = 1e-5
PAIR_EPS = 1e-8
#: Weaver's JetClass ``pf_features``, in its order.
FEATURES = ("pt_log", "e_log", "logptrel", "logerel", "deltaR", "charge",
            "isChargedHadron", "isNeutralHadron", "isPhoton", "isElectron",
            "isMuon", "d0", "d0err", "dz", "dzerr", "deta", "dphi")
#: Particle species: share, mass (GeV), charged.
SPECIES = {"isChargedHadron": (0.60, 0.13957, True),
           "isPhoton": (0.25, 0.0, False),
           "isNeutralHadron": (0.10, 0.49761, False),
           "isElectron": (0.03, 0.000511, True),
           "isMuon": (0.02, 0.10566, True)}
#: Jets per numpy batch of the reference: bounds its memory at 128 slots.
CHUNK = 64
#: The attention kernel's ``pallas_call`` name, in its HLO instruction's.
KERNEL = "flash_attn"


def _wrap(phi: np.ndarray) -> np.ndarray:
    return np.mod(phi + np.pi, 2 * np.pi) - np.pi


def jets(cfg: dict, n: int, seed) -> np.ndarray:
    """(n, particles, 22) float32 jets drawn from ``seed`` (see the
    configuration's ``assumed.inputs``)."""
    rng = np.random.default_rng(seed)
    slots = cfg["particles"]
    m = cfg["multiplicity"]
    mult = np.clip(np.rint(rng.lognormal(math.log(m["median"]), m["sigma"],
                                         n)), m["min"], slots).astype(int)
    valid = np.arange(slots)[None, :] < mult[:, None]
    jet_pt = rng.uniform(500.0, 1000.0, n)[:, None]
    jet_y = rng.uniform(-2.0, 2.0, n)[:, None]
    jet_phi = rng.uniform(-np.pi, np.pi, n)[:, None]
    # pT shares, largest first: 0.5 GeV each, the rest split by Exp(1)^2.
    w = np.where(valid, rng.exponential(1.0, (n, slots)) ** 2, 0.0)
    w = -np.sort(-w, axis=1)
    pt = np.where(valid, 0.5 + (jet_pt - 0.5 * mult[:, None])
                  * w / w.sum(axis=1, keepdims=True), 0.0)
    rank = np.arange(slots)[None, :] / mult[:, None]
    spread = 0.05 + 0.25 * rank
    dy = np.clip(rng.normal(0.0, 1.0, (n, slots)) * spread, -0.79, 0.79)
    dphi = np.clip(rng.normal(0.0, 1.0, (n, slots)) * spread, -0.79, 0.79)
    names = list(SPECIES)
    kind = rng.choice(len(names), (n, slots),
                      p=[SPECIES[k][0] for k in names])
    mass = np.array([SPECIES[k][1] for k in names])[kind]
    charged = np.array([SPECIES[k][2] for k in names])[kind]
    y, phi = jet_y + dy, _wrap(jet_phi + dphi)
    mt = np.sqrt(pt ** 2 + mass ** 2)
    px, py = pt * np.cos(phi), pt * np.sin(phi)
    pz, e = mt * np.sinh(y), mt * np.cosh(y)
    v = np.where(valid[..., None], np.stack([px, py, pz, e], -1), 0.0)
    jet = v.sum(axis=1)
    j_pt = np.hypot(jet[:, 0], jet[:, 1])[:, None]
    j_eta = np.arcsinh(jet[:, 2:3] / j_pt)
    j_phi = np.arctan2(jet[:, 1], jet[:, 0])[:, None]
    safe_pt = np.where(valid, pt, 1.0)
    safe_e = np.where(valid, e, 1.0)
    deta = (np.arcsinh(pz / safe_pt) - j_eta) * np.sign(j_eta)
    dphi_j = _wrap(phi - j_phi)
    charge = np.where(charged, rng.choice([-1.0, 1.0], (n, slots)), 0.0)
    track = lambda sd: np.where(charged, rng.normal(0.0, sd, (n, slots)), 0.0)
    err = lambda: np.where(charged, np.clip(np.abs(
        rng.normal(0.02, 0.02, (n, slots))), 0.0, 1.0), 0.0)
    cols = {"pt_log": (np.log(safe_pt) - 1.7) * 0.7,
            "e_log": (np.log(safe_e) - 2.0) * 0.7,
            "logptrel": (np.log(safe_pt / j_pt) + 4.7) * 0.7,
            "logerel": (np.log(safe_e / jet[:, 3:4]) + 4.7) * 0.7,
            "deltaR": (np.hypot(deta, dphi_j) - 0.2) * 4.0,
            "charge": charge,
            "d0": np.tanh(track(0.05)), "d0err": err(),
            "dz": np.tanh(track(0.1)), "dzerr": err(),
            "deta": deta, "dphi": dphi_j}
    for i, k in enumerate(names):
        cols[k] = (kind == i).astype(float)
    feats = np.stack([cols[k] for k in FEATURES], -1)
    out = np.concatenate([feats, v, valid[..., None]], -1)
    return np.where(valid[..., None], out, 0.0).astype(np.float32)


# -- the numpy reference ---------------------------------------------------

def _f8(a: np.ndarray) -> np.ndarray:
    return a.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)


def _mm(a, b, low=False):
    return np.matmul(_f8(a), _f8(b)) if low else np.matmul(a, b)


def _gelu(x):
    y = erf(x * np.float32(1 / math.sqrt(2.0)))
    y += 1.0
    y *= x
    y *= 0.5
    return y


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = np.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + NORM_EPS) * p["gamma"] + p["beta"]


def _bn(x, p):
    return (x - p["mean"]) / np.sqrt(p["var"] + NORM_EPS) * p["gamma"] \
        + p["beta"]


def _dense(x, p, low):
    return _mm(x, p["w"], low) + p["b"]


def pair_features(vi: np.ndarray, vj: np.ndarray) -> np.ndarray:
    """weaver's ``pairwise_lv_fts`` with four outputs: (..., 4) four-vectors
    of particles i and j -> (..., 4) [ln kT, ln z, ln Delta, ln m^2], in
    float64 from the float32 inputs and returned in float32. Weaver's
    difference of squares for m^2 is kept; in float64 it is exact to far
    below the float32 rounding of a light particle's mass."""
    vi, vj = vi.astype(np.float64), vj.astype(np.float64)
    def pt_rap_phi(v):
        px, py, pz, e = (v[..., k] for k in range(4))
        rap = 0.5 * np.log(1 + 2 * pz / np.maximum(e - pz, 1e-20))
        return np.sqrt(px * px + py * py), rap, np.arctan2(py, px)
    pti, rapi, phii = pt_rap_phi(vi)
    ptj, rapj, phij = pt_rap_phi(vj)
    delta = np.sqrt(np.square(rapi - rapj) + np.square(_wrap(phii - phij)))
    ptmin = np.minimum(pti, ptj)
    s = vi + vj
    m2 = np.square(s[..., 3]) - np.square(s[..., :3]).sum(-1)
    return np.stack([
        np.log(np.maximum(ptmin * delta, PAIR_EPS)),
        np.log(np.maximum(ptmin / np.maximum(pti + ptj, PAIR_EPS),
                          PAIR_EPS)),
        np.log(np.maximum(delta, PAIR_EPS)),
        np.log(np.maximum(m2, PAIR_EPS))], -1).astype(np.float32)


def _pairs(v):
    """(m, n, 4) -> the pairs i >= j, weaver's order: (m, P, 4), i, j."""
    i, j = np.tril_indices(v.shape[1])
    return pair_features(v[:, i], v[:, j]), i, j


def _pair_embed(p, f, low):
    y = _bn(f, p["bn"])
    for k, layer in enumerate(p["layers"]):
        y = _bn(_dense(y, layer, low), layer["bn"])
        if k < len(p["layers"]) - 1:
            y = _gelu(y)
    return y


def _attention(q, k, v, bias, low):
    """(m, H, s, d), (m, H, t, d) -> (m, H, s, d)."""
    s = _mm(q, np.swapaxes(k, -1, -2), low) / np.float32(
        math.sqrt(q.shape[-1]))
    if bias is not None:
        s = s + bias
    s = np.exp(s - s.max(-1, keepdims=True))
    return _mm(s / s.sum(-1, keepdims=True), v, low)


def _block(p, x, bias, low, cls=None):
    m, c = x.shape[0], x.shape[-1]
    h = p["head_scale"].shape[0]
    heads = lambda a: a.reshape(m, a.shape[1], h, c // h).transpose(0, 2, 1, 3)
    w, b = p["qkv"]["w"], p["qkv"]["b"]
    if cls is None:
        residual = x
        qkv = _dense(_ln(x, p["ln_attn"]), p["qkv"], low)
        q, k, v = (heads(t) for t in np.split(qkv, 3, axis=-1))
    else:
        residual = cls
        u = _ln(np.concatenate([cls, x], axis=1), p["ln_attn"])
        q = heads(_mm(cls, w[:, :c], low) + b[:c])
        k, v = (heads(t) for t in np.split(_mm(u, w[:, c:], low) + b[c:], 2,
                                           axis=-1))
    o = _attention(q, k, v, bias, low).transpose(0, 2, 1, 3).reshape(m, -1, c)
    o = _dense(o, p["out"], low)
    # weaver: view (t, b, h, d); einsum 'tbhd,h->tbdh'; reshape (t, b, c).
    o = (o.reshape(m, -1, h, c // h) * p["head_scale"][:, None]
         ).transpose(0, 1, 3, 2).reshape(m, -1, c)
    x = residual + _ln(o, p["ln_post_attn"])
    y = _gelu(_dense(_ln(x, p["ln_fc"]), p["fc1"], low))
    y = _dense(_ln(y, p["ln_post_fc"]), p["fc2"], low)
    return y + p["resid_scale"] * x


def _forward(params, ev, low=False, pair_bias=True):
    """(m, n, 22) jets of n real particles each -> (m, 1, classes)."""
    m, n = ev.shape[:2]
    x = _bn(ev[..., :17], params["embed"]["bn"])
    for layer in params["embed"]["layers"]:
        x = _gelu(_dense(_ln(x, layer["ln"]), layer, low))
    u = None
    if pair_bias:
        f, i, j = _pairs(ev[..., 17:21])
        e = _pair_embed(params["pair_embed"], f, low).transpose(0, 2, 1)
        u = np.zeros((m, e.shape[1], n, n), np.float32)
        u[:, :, i, j] = e
        u[:, :, j, i] = e
    for p in params["blocks"]:
        x = _block(p, x, u, low)
    cls = np.broadcast_to(params["cls_token"], (m, 1, x.shape[-1]))
    for p in params["cls_blocks"]:
        cls = _block(p, x, None, low, cls=cls)
    return _dense(_ln(cls, params["norm"]), params["fc"], low)


def reference(cfg: dict, model: dict, x: np.ndarray, *, low: bool = False,
              pair_bias: bool = True) -> np.ndarray:
    """(n, 1, classes) float32 logits for (n, particles, 22) jets.

    ``low``: every matrix-product input rounded to float8 e4m3 (the control);
    ``pair_bias=False``: the attention without U (a check that U matters).
    """
    lengths = (x[..., 21] > 0).sum(axis=1)
    out = np.empty((len(x), 1, cfg["num_classes"]), np.float32)
    chunks = [(n, rows) for n in np.unique(lengths)
              for rows in np.array_split(np.flatnonzero(lengths == n), -(
                  -np.count_nonzero(lengths == n) // CHUNK))]

    def run(chunk):
        n, rows = chunk
        with np.errstate(over="ignore", invalid="ignore"):
            out[rows] = _forward(model["params"], x[rows, :n], low, pair_bias)

    # numpy's loops release the interpreter lock: one thread per core,
    # each with a single-threaded BLAS.
    with threadpoolctl.threadpool_limits(1, "blas"), \
            concurrent.futures.ThreadPoolExecutor(os.cpu_count()) as pool:
        for f in [pool.submit(run, c) for c in chunks]:
            f.result()
    return out


def control(cfg: dict, model: dict, x: np.ndarray) -> np.ndarray:
    """The reference with every matrix-product input in float8 e4m3, one
    precision below bf16."""
    return reference(cfg, model, x, low=True)


def mismatched(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """A jet whose logits differ from the reference's by more than
    ``LOGIT_TOL`` in any class."""
    err = np.abs(got.astype(np.float64) - want.astype(np.float64))
    return (err.reshape(len(got), -1) > LOGIT_TOL).any(axis=1)


# -- weights ---------------------------------------------------------------

def _norm(n):
    return {"gamma": np.ones(n, np.float32), "beta": np.zeros(n, np.float32)}


def _batchnorm(n):
    return dict(_norm(n), mean=np.zeros(n, np.float32),
                var=np.ones(n, np.float32))


def _linear(rng, n_in, n_out):
    bound = 1.0 / math.sqrt(n_in)
    return {"w": rng.uniform(-bound, bound, (n_in, n_out)).astype(np.float32),
            "b": rng.uniform(-bound, bound, n_out).astype(np.float32)}


def _block_weights(rng, c, heads, ffn):
    bound = math.sqrt(6.0 / (4 * c))
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


def _set_stats(bn, rows):
    bn["mean"] = rows.mean(0).astype(np.float32)
    bn["var"] = rows.var(0).astype(np.float32)


def build(cfg: dict, seed: int) -> dict:
    """torch's default initialisation from ``seed``, then every BatchNorm's
    running mean and variance from a seeded calibration batch of jets."""
    rng = np.random.default_rng([seed, 1])
    embed, d = [], cfg["input_dim"]
    for n in cfg["embed_dims"]:
        embed.append(dict(_linear(rng, d, n), ln=_norm(d)))
        d = n
    pair, d = [], cfg["pair_input_dim"]
    for n in list(cfg["pair_embed_dims"]) + [cfg["num_heads"]]:
        pair.append(dict(_linear(rng, d, n), bn=_batchnorm(n)))
        d = n
    c = cfg["embed_dims"][-1]
    block = lambda: _block_weights(rng, c, cfg["num_heads"],
                                   cfg["ffn_ratio"] * c)
    params = {"embed": {"bn": _batchnorm(cfg["input_dim"]), "layers": embed},
              "pair_embed": {"bn": _batchnorm(cfg["pair_input_dim"]),
                             "layers": pair},
              "blocks": [block() for _ in range(cfg["num_layers"])],
              "cls_blocks": [block() for _ in range(cfg["num_cls_layers"])],
              "cls_token": rng.normal(0.0, 0.02, c).astype(np.float32),
              "norm": _norm(c), "fc": _linear(rng, c, cfg["num_classes"])}
    cal = jets(cfg, cfg["calibration_events"], [seed, 2])
    valid = cal[..., 21] > 0
    _set_stats(params["embed"]["bn"], cal[valid][:, :17])
    y = np.concatenate([_pairs(ev[None, :n, 17:21])[0][0] for ev, n in
                        zip(cal, valid.sum(axis=1))])
    _set_stats(params["pair_embed"]["bn"], y)
    y = _bn(y, params["pair_embed"]["bn"])
    for k, layer in enumerate(pair):
        z = _dense(y, layer, False)
        _set_stats(layer["bn"], z)
        y = _bn(z, layer["bn"])
        if k < len(pair) - 1:
            y = _gelu(y)
    return {"params": params}


def tenant(cfg: dict, model: dict, name: str):
    """The program's ``TenantSpec`` serving these weights."""
    from repro.serve import part_model
    from repro.serve.fleet import TenantSpec
    return TenantSpec(name=name, model=part_model(model["params"]))


def inputs(cfg: dict, model: dict, n: int, seed) -> np.ndarray:
    return jets(cfg, n, seed)


# -- counts ----------------------------------------------------------------

def _pairs_padded(cfg: dict) -> int:
    n = cfg["particles"]
    return n * (n + 1) // 2


def attn_ops(cfg: dict) -> int:
    """Operations of the attention kernel per jet and particle block, as it
    computes them at the padded length: q k^T and p v, 2 x 2 H N^2 d_h."""
    n, c = cfg["particles"], cfg["embed_dims"][-1]
    return 2 * 2 * n * n * c


def attn_bytes(cfg: dict) -> int:
    """Bytes the attention kernel moves per jet and particle block: q, k, v
    and the output in bf16, U in f32, the key mask in int32 (per head)."""
    n, c, h = cfg["particles"], cfg["embed_dims"][-1], cfg["num_heads"]
    return 4 * n * c * 2 + h * n * n * 4 + h * n * 4


def attn_calls(cfg: dict, trace) -> list:
    """(jets, device us) of each call of the attention kernel in the traced
    window; the jets of a call from its output's shape (jets, heads,
    particles, head size), so a padded batch counts as the kernel ran it."""
    import tracereduce
    if trace is None:
        return []
    per_jet = cfg["particles"] * cfg["embed_dims"][-1]
    calls = []
    for name, us in trace["op_calls_us"].items():
        m = tracereduce.HLO_TEXT.match(name)
        if not m or m.group(3) != "custom-call" or KERNEL not in m.group(1):
            continue
        dims = re.search(r"\[([\d,]+)\]", m.group(2))
        jets = math.prod(int(d) for d in dims.group(1).split(",")) // per_jet
        calls.extend((jets, t) for t in us)
    return calls


def macs_per_event(cfg: dict) -> int:
    """Multiply-accumulates of one jet at the padded length, the work the
    program does: embedding, pair embedding over the pairs i >= j, the
    particle and class blocks, the head."""
    n, c = cfg["particles"], cfg["embed_dims"][-1]
    macs, d = 0, cfg["input_dim"]
    for k in cfg["embed_dims"]:
        macs += n * d * k
        d = k
    d = cfg["pair_input_dim"]
    for k in list(cfg["pair_embed_dims"]) + [cfg["num_heads"]]:
        macs += _pairs_padded(cfg) * d * k
        d = k
    ffn = 2 * c * cfg["ffn_ratio"] * c
    block = n * (3 * c * c + c * c + ffn) + attn_ops(cfg) // 2
    cls = (c * c + (n + 1) * 2 * c * c + 2 * (n + 1) * c + c * c + ffn)
    macs += cfg["num_layers"] * block + cfg["num_cls_layers"] * cls
    return macs + c * cfg["num_classes"]


def min_bytes(cfg: dict, batch: int) -> int:
    """Input and output of the batch, and every weight once (matrices in
    bf16, the rest f32)."""
    c, h = cfg["embed_dims"][-1], cfg["num_heads"]
    ffn = cfg["ffn_ratio"] * c
    mats, vectors, d = 0, 4 * cfg["input_dim"], cfg["input_dim"]
    for k in cfg["embed_dims"]:
        mats, vectors, d = mats + d * k, vectors + 2 * d + k, k
    vectors, d = vectors + 4 * cfg["pair_input_dim"], cfg["pair_input_dim"]
    for k in list(cfg["pair_embed_dims"]) + [h]:
        mats, vectors, d = mats + d * k, vectors + 5 * k, k
    blocks = cfg["num_layers"] + cfg["num_cls_layers"]
    mats += blocks * (4 * c * c + 2 * c * ffn) + c * cfg["num_classes"]
    vectors += (blocks * (11 * c + 3 * ffn + h) + 3 * c
                + cfg["num_classes"])
    event_in = cfg["particles"] * (cfg["input_dim"] + 5) * 4
    return (batch * (event_in + cfg["num_classes"] * 4) + 2 * mats
            + 4 * vectors)
