"""The Particle Transformer served through FleetServer (repro.models.part,
repro.serve.part_model) against its plain reference, at the published
widths with 16 particle slots, on the CPU (attention kernel interpreted)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cascade_mlp import cascade_mlp, deepsets
from repro.models import part
from repro.serve import JetServer, part_model
from repro.serve.fleet import FleetServer, TenantSpec

SLOTS = 16
#: Served logits against the float32 reference. Serving rounds every
#: matrix-product input to bfloat16 (a relative step of 2**-8) through ten
#: blocks; logits of about 0.5 then move by 0.01 to 0.03, a sixteenth of
#: what float8 products move them (the benchmark's ParT kind reads both).
LOGIT_TOL = 0.08


def _jet(rng, n, slots=SLOTS):
    """One jet of ``n`` particles near an axis at phi = 3 (so pairs straddle
    +-pi), padded with zeros to ``slots``."""
    ev = np.zeros((slots, part.N_COLUMNS), np.float32)
    pt = np.sort(rng.exponential(20.0, n))[::-1] + 0.5
    eta = rng.normal(0.0, 0.3, n)
    phi = rng.normal(0.0, 0.3, n) + 3.0
    mass = rng.choice([0.0, 0.13957], n)
    px, py, pz = pt * np.cos(phi), pt * np.sin(phi), pt * np.sinh(eta)
    ev[:n, :part.N_FEATURES] = rng.normal(0.0, 1.0, (n, part.N_FEATURES))
    ev[:n, part.N_FEATURES:] = np.stack(
        [px, py, pz, np.sqrt(px ** 2 + py ** 2 + pz ** 2 + mass ** 2),
         np.ones(n)], -1)
    return ev


@pytest.fixture(scope="module")
def params():
    return part.init_params(3)


@pytest.fixture(scope="module")
def served(params):
    return jax.jit(jax.vmap(part.part_forward, in_axes=(None, 0))), \
        part.serving_params(params)


def test_fleet_serves_part_as_the_reference(params):
    rng = np.random.default_rng(0)
    events = np.stack([_jet(rng, n) for n in (5, 16, 11)])
    fleet = FleetServer([TenantSpec(name="part",
                                    model=part_model(params))])
    try:
        got = np.stack([r.wait(120) for r in
                        [fleet.submit(e, "part") for e in events]])
    finally:
        fleet.close()
    want = np.asarray(jax.vmap(part.part_ref, in_axes=(None, 0))(
        params, events))
    assert got.shape == (3, 1, 10) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_logits_invariant_under_permutation_of_particles(served):
    fn, sp = served
    rng = np.random.default_rng(1)
    ev = _jet(rng, 12)
    perm = ev.copy()
    perm[:12] = ev[rng.permutation(12)]
    a, b = np.asarray(fn(sp, np.stack([ev, perm])))
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)


def test_padded_slots_change_nothing(served):
    fn, sp = served
    ev = _jet(np.random.default_rng(2), 10)
    wider = np.zeros((2 * SLOTS, part.N_COLUMNS), np.float32)
    wider[:SLOTS] = ev
    a = np.asarray(fn(sp, ev[None]))
    b = np.asarray(fn(sp, wider[None]))
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)


def test_pair_features_by_hand():
    """Two particles: a massless one at phi = 3.0 and a pion at phi = -3.0,
    whose Delta-phi wraps to 6 - 2 pi; self-pairs hit the eps clamps."""
    m = 0.13957
    p1 = (5.0 * math.cos(3.0), 5.0 * math.sin(3.0), 2.0)
    p2 = (2.0 * math.cos(-3.0), 2.0 * math.sin(-3.0), -1.0)
    e1 = math.sqrt(sum(c * c for c in p1))
    e2 = math.sqrt(sum(c * c for c in p2) + m * m)
    v = np.array([p1 + (e1,), p2 + (e2,)], np.float32)
    f = np.asarray(part.pair_features(jnp.asarray(v)))

    def rap(p, e):
        return 0.5 * math.log((e + p[2]) / (e - p[2]))
    dphi = 6.0 - 2 * math.pi
    delta = math.hypot(rap(p1, e1) - rap(p2, e2), dphi)
    s = [a + b for a, b in zip(p1 + (e1,), p2 + (e2,))]
    m2 = s[3] ** 2 - s[0] ** 2 - s[1] ** 2 - s[2] ** 2
    eps = math.log(1e-8)
    want_01 = [math.log(2.0 * delta), math.log(2.0 / 7.0), math.log(delta),
               math.log(m2)]
    np.testing.assert_allclose(f[:, 0, 1], want_01, atol=2e-5)
    np.testing.assert_allclose(f[:, 1, 0], want_01, atol=2e-5)
    np.testing.assert_allclose(f[:, 0, 0], [eps, math.log(0.5), eps, eps],
                               atol=2e-5)
    np.testing.assert_allclose(f[:, 1, 1], [eps, math.log(0.5), eps,
                                            math.log(4 * m * m)], atol=2e-4)


def test_int8_tenants_build_the_same_program():
    """The served-model entry point leaves the int8 tenants' programs as
    they were: the same lowered program as jit(vmap(kernel))."""
    from repro.quant import quantize_mlp
    rng = np.random.default_rng(4)

    def qmlp(sizes, relu_last):
        ws = [rng.normal(0, 0.5 / np.sqrt(k), (k, n))
              for k, n in zip(sizes[:-1], sizes[1:])]
        bs = [rng.normal(0, 0.1, n) for n in sizes[1:]]
        relus = [True] * (len(ws) - 1) + [relu_last]
        return quantize_mlp(ws, bs, relus, rng.normal(0, 1, (64, sizes[0])))

    phi, rho, mlp = (qmlp([8, 16, 16], True), qmlp([16, 5], False),
                     qmlp([8, 16, 5], False))
    x = jnp.zeros((3, 16, 8), jnp.int8)
    cases = [
        (JetServer(phi, rho=rho, agg="mean", interpret=True),
         lambda e: deepsets(e, phi, rho, agg="mean", interpret=True)),
        (JetServer(mlp, interpret=True),
         lambda e: cascade_mlp(e, mlp, interpret=True))]
    for server, kernel in cases:
        try:
            assert server.model is None
            assert (server._fn.lower(x).as_text()
                    == jax.jit(jax.vmap(kernel)).lower(x).as_text())
        finally:
            server.close()
