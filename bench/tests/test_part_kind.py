"""The ParT kind's numpy reference against the program's own reference, and
its tolerance against the float8 control and the reference without U.

Runs on the CPU in well under a minute; not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_part_kind.py
"""
from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402

CELL = "part-128.drain"


@pytest.fixture(scope="module")
def case():
    cell = harness.resolve(CELL)
    kind, cfg = cell["kind"], cell["config"]
    model = kind.build(cfg, cfg["weights_seed"])
    x = kind.inputs(cfg, model, 8, seed=[2 ** 33 + 5, 3])
    return kind, cfg, model, x, kind.reference(cfg, model, x)


def test_numpy_reference_is_the_program_reference(case):
    """Two float32 references, one at each jet's length and one padded and
    masked: they agree to float32 rounding through ten blocks."""
    import jax
    import jax.numpy as jnp
    from repro.models.part import part_ref
    kind, cfg, model, x, want = case
    params = jax.tree.map(jnp.asarray, model["params"])
    got = np.asarray(jax.vmap(part_ref, in_axes=(None, 0))(params, x))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def test_control_and_reference_without_u_fail_the_tolerance(case):
    kind, cfg, model, x, want = case
    assert not kind.mismatched(want, want).any()
    control = kind.control(cfg, model, x)
    no_u = kind.reference(cfg, model, x, pair_bias=False)
    assert kind.mismatched(control, want).mean() > 0.5
    assert kind.mismatched(no_u, want).mean() > 0.5
