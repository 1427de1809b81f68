"""Compile the served Pallas kernels for a TPU v5e without a chip.

Each test lowers and compiles one served function at its real widths for a
described (not attached) v5e device and checks that the compiled program
holds a Mosaic kernel (``tpu_custom_call``). This catches what interpret
mode cannot: lowering rules Mosaic lacks, unaligned slices, VMEM overruns.
A passing compile says nothing about results or times.

The topology is described inside a fixture, never while a module imports:
only one process may load the TPU library, and pytest-xdist workers all
import every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cascade_mlp import cascade_mlp, deepsets, mlp_unfused
from repro.kernels.global_agg import global_agg
from repro.quant import quantize_mlp

# Published widths, as served by repro.launch.serve (Table 3).
MLPS = {"jsc-m": [16, 64, 32, 32, 32, 5], "jsc-xl": [16, 128, 64, 64, 64, 5]}
DEEPSETS = {"deepsets-32": (32, [21, 32, 32, 32], [32, 10]),
            "deepsets-64": (64, [21, 64, 64, 64], [64, 10])}
PARTICLES = 64  # constituents per JSC event
BATCHES = [1, 64]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_text(one_chip):
    """Compile ``fn`` for one v5e chip on int8 inputs; return its HLO text.

    The persistent compile cache is off meanwhile: an entry written for a
    described chip cannot be read back without one.
    """
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, jnp.int8, sharding=one_chip)
                for s in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _qmlp(sizes, relu_last=False, seed=0):
    rng = np.random.default_rng(seed)
    ws = [rng.normal(0, 0.5 / np.sqrt(k), (k, n))
          for k, n in zip(sizes[:-1], sizes[1:])]
    bs = [rng.normal(0, 0.1, n) for n in sizes[1:]]
    relus = [True] * (len(ws) - 1) + [relu_last]
    return quantize_mlp(ws, bs, relus, rng.normal(0, 1, (256, sizes[0])))


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("name", sorted(MLPS))
def test_cascade_mlp_compiles(compile_text, name, batch):
    q = _qmlp(MLPS[name])
    text = compile_text(jax.vmap(lambda x: cascade_mlp(x, q)),
                        (batch, PARTICLES, MLPS[name][0]))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("name", sorted(DEEPSETS))
def test_deepsets_compiles(compile_text, name, batch):
    m, phi_sizes, rho_sizes = DEEPSETS[name]
    phi, rho = _qmlp(phi_sizes, relu_last=True), _qmlp(rho_sizes, seed=1)
    text = compile_text(jax.vmap(lambda x: deepsets(x, phi, rho, agg="mean")),
                        (batch, m, phi_sizes[0]))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", BATCHES)
def test_mlp_unfused_compiles(compile_text, batch):
    q = _qmlp(MLPS["jsc-m"])
    text = compile_text(jax.vmap(lambda x: mlp_unfused(x, q)),
                        (batch, PARTICLES, MLPS["jsc-m"][0]))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op,impl", [("sum", "mac"), ("mean", "mac"),
                                     ("sum", "extract_add")])
@pytest.mark.parametrize("m,f", [(32, 32), (64, 64)])
def test_global_agg_compiles(compile_text, op, impl, m, f):
    text = compile_text(lambda x: global_agg(x, op=op, impl=impl), (m, f))
    assert "tpu_custom_call" in text


def _served(name):
    """The served function and input shape for each named kernel."""
    if name == "cascade_mlp":
        q = _qmlp(MLPS["jsc-m"])
        return (jax.vmap(lambda x: cascade_mlp(x, q)),
                (64, PARTICLES, MLPS["jsc-m"][0]))
    if name == "deepsets":
        m, phi_sizes, rho_sizes = DEEPSETS["deepsets-32"]
        phi, rho = _qmlp(phi_sizes, relu_last=True), _qmlp(rho_sizes, seed=1)
        return (jax.vmap(lambda x: deepsets(x, phi, rho, agg="mean")),
                (64, m, phi_sizes[0]))
    if name == "mm_int8":
        q = _qmlp(MLPS["jsc-m"])
        return (jax.vmap(lambda x: mlp_unfused(x, q)),
                (64, PARTICLES, MLPS["jsc-m"][0]))
    return (lambda x: global_agg(x, op="mean", impl="mac"), (32, 32))


@pytest.mark.parametrize("name", ["cascade_mlp", "deepsets", "mm_int8",
                                  "global_agg"])
def test_kernel_is_named_in_the_compiled_program(compile_text, name):
    """The profiler names a device operation by its HLO instruction: the
    kernel's ``pallas_call`` name has to reach the custom call's."""
    fn, shape = _served(name)
    text = compile_text(fn, shape)
    calls = [ln.split(" = ")[0].strip() for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert calls and all(name in c for c in calls), calls


def test_part_compiles_with_its_named_kernel(compile_text, one_chip,
                                             monkeypatch):
    """The served ParT at its published widths, 128 particles, a batch of
    64: the attention kernel compiles for the chip (interpret mode off, as
    on a TPU) and is the program's only kernel, once per particle block."""
    from repro.launch import platform
    from repro.models import part
    from repro.serve import part_jet
    monkeypatch.setattr(platform, "interpret", lambda: False)
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(spec, part.serving_params(part.init_params(0)))
    event = jax.ShapeDtypeStruct((64, 128, part.N_COLUMNS), jnp.float32,
                                 sharding=one_chip)
    text = jax.jit(jax.vmap(part_jet, in_axes=(0, None))).lower(
        event, params).compile().as_text()
    calls = [ln.split(" = ")[0].strip() for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert len(calls) == 8 and all("flash_attn" in c for c in calls), calls
