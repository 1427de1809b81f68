"""End-to-end system behaviour tests: serving engine, device and compile
cache helpers, train auto-resume, gradient compression, fault-tolerance
watchdog."""
import importlib.util
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ckpt as ckpt_lib
from repro import optim
from repro.configs import get_reduced
from repro.data import JetConfig, jet_batch
from repro.distributed import compression
from repro.distributed.ft import StepWatchdog, WatchdogConfig
from repro.distributed.steps import make_train_step
from repro.launch import platform, serve
from repro.models import build
from repro.models import deepsets as ds
from repro.serve import JetServer


REPO = pathlib.Path(__file__).resolve().parents[1]


def _quantize_inputs(x, e_in):
    return np.clip(np.round(x / 2.0 ** e_in), -128, 127).astype(np.int8)


class TestServing:
    def test_fused_server_matches_oracle(self):
        """The deployed fused kernel must be bit-identical to the reference
        engine on the same quantized model (INT8 is exact)."""
        key = jax.random.key(0)
        params = ds.deepsets_init(key, 8, [16, 16], [16, 5])
        x, _ = jet_batch(JetConfig(n_particles=8, n_features=8, n_classes=5),
                         32, 1)
        qphi, qrho = ds.to_quantized(params, x[:16])
        fused = JetServer(qphi, rho=qrho, mode="fused", interpret=True,
                          window_us=50.0)
        ref = JetServer(qphi, rho=qrho, mode="ref", window_us=50.0)
        xq = _quantize_inputs(x, qphi.e_in)
        try:
            for i in range(4):
                a = fused.infer(xq[i])
                b = ref.infer(xq[i])
                np.testing.assert_array_equal(a, b)
        finally:
            fused.close()
            ref.close()

    def test_server_batches_requests(self):
        key = jax.random.key(1)
        params = ds.deepsets_init(key, 8, [16, 16], [16, 5])
        x, _ = jet_batch(JetConfig(n_particles=8, n_features=8, n_classes=5),
                         64, 2)
        qphi, qrho = ds.to_quantized(params, x[:16])
        srv = JetServer(qphi, rho=qrho, mode="ref", max_batch=16,
                        window_us=20_000.0)
        try:
            xq = _quantize_inputs(x, qphi.e_in)
            reqs = [srv.submit(xq[i]) for i in range(16)]
            for r in reqs:
                assert r.event.wait(30)
            assert max(srv.stats.batch_sizes) > 1, "no batching happened"
        finally:
            srv.close()

    def test_model_error_raises_in_caller(self):
        """An event the jitted model cannot take makes ``infer`` raise the
        tracer's own error at once, not a TimeoutError after the wait."""
        params = ds.deepsets_init(jax.random.key(2), 8, [16, 16], [16, 5])
        x, _ = jet_batch(JetConfig(n_particles=8, n_features=8, n_classes=5),
                         16, 3)
        qphi, qrho = ds.to_quantized(params, x)
        srv = JetServer(qphi, rho=qrho, mode="ref", window_us=50.0)
        try:
            wrong = np.zeros((8, 5), np.int8)  # 5 features, the model wants 8
            t0 = time.perf_counter()
            with pytest.raises(TypeError, match="dot_general"):
                srv.infer(wrong, timeout=30)
            assert time.perf_counter() - t0 < 10
            xq = _quantize_inputs(x, qphi.e_in)
            assert srv.infer(xq[0], timeout=30).shape == (1, 5)
        finally:
            srv.close()


@pytest.fixture
def jax_cache_config():
    """Restore JAX's persistent-cache settings after a test changes them."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


class TestPlatform:
    def test_cpu_backend_interprets(self):
        assert os.environ.get("JAX_PLATFORMS") == "cpu"
        assert platform.interpret() is True
        info = platform.device_info()
        assert info["platform"] == "cpu" and info["count"] >= 1
        params = ds.deepsets_init(jax.random.key(3), 8, [16], [5])
        x, _ = jet_batch(JetConfig(n_particles=8, n_features=8, n_classes=5),
                         4, 4)
        qphi, qrho = ds.to_quantized(params, x)
        srv = JetServer(qphi, rho=qrho)
        srv.close()
        assert srv.interpret is True

    def test_compile_cache_honours_env(self, monkeypatch, tmp_path,
                                       jax_cache_config):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert platform.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper sets no other directory
        assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0

    def test_compile_cache_default_is_fixed_in_checkout(self, monkeypatch,
                                                        jax_cache_config):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first = platform.enable_compile_cache()
        assert platform.enable_compile_cache() == first
        assert first == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

    def test_chip_smoke_body_matches_reference(self, monkeypatch, tmp_path,
                                               jax_cache_config):
        """The smoke's body at a few events, interpreted on the CPU: every
        served output equals the jnp reference."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", REPO / "chip_smoke.py")
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        reports = smoke.run(events=4, train_steps=1)
        assert [r["model"] for r in reports] == list(smoke.MODELS)
        for r in reports:
            assert r["failures"] == []
            assert r["events"] == 4 + serve.SINGLE_EVENTS
            assert r["exact"] == r["events"]
            assert r["tpu_custom_call"] is False


class TestTrainResume:
    def test_auto_resume_continues_from_checkpoint(self, tmp_path):
        cfg = get_reduced("xlstm-350m")
        model = build(cfg)
        ocfg = optim.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
        step_fn = jax.jit(make_train_step(cfg, ocfg))
        params = model.init(jax.random.key(0))
        opt = optim.init(params)
        batch = {"tokens": jnp.ones((2, 16), jnp.int32),
                 "labels": jnp.ones((2, 16), jnp.int32)}

        for step in range(3):
            params, opt, _ = step_fn(params, opt, batch)
        ckpt_lib.save(str(tmp_path), 3, (params, opt))
        # "crash": restore into same-structure state
        (params2, opt2), step, _ = ckpt_lib.restore(
            str(tmp_path), (params, opt))
        assert step == 3
        assert int(opt2.step) == 3
        a = jax.tree.leaves(params)[0]
        b = jax.tree.leaves(params2)[0]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # training continues from the restored state
        params3, opt3, m = step_fn(params2, opt2, batch)
        assert int(opt3.step) == 4
        assert np.isfinite(float(m["loss"]))

    def test_uncommitted_checkpoint_invisible(self, tmp_path):
        tree = {"w": jnp.ones((4,))}
        d = ckpt_lib.save(str(tmp_path), 1, tree)
        os.remove(os.path.join(d, ckpt_lib.COMMIT))
        assert ckpt_lib.latest_step(str(tmp_path)) is None


class TestGradientCompression:
    def test_error_feedback_preserves_signal(self):
        """Int8+EF compression: the accumulated decompressed signal tracks
        the accumulated true gradient (residual carried, not lost)."""
        rng = np.random.default_rng(0)
        g_true = jnp.asarray(rng.normal(0, 1e-3, (128,)), jnp.float32)
        err = jnp.zeros_like(g_true)
        acc = jnp.zeros_like(g_true)
        s = jnp.float32(1.0)
        for _ in range(50):
            q, s, err = compression.compress(g_true, err)
            acc = acc + compression.decompress(q, s)
        total = 50.0 * g_true
        # the running sum stays within one quantization quantum of truth
        resid = float(jnp.max(jnp.abs(acc - total)))
        assert resid <= float(s) + 1e-6

    def test_compressed_psum_single_axis(self):
        mesh = jax.make_mesh((1,), ("pod",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = {"w": jnp.arange(8, dtype=jnp.float32) * 1e-2}
        e = compression.init_error_state(g)

        def f(g, e):
            return compression.compressed_psum(g, e, "pod")

        g2, _ = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(),) * 2,
            out_specs=(jax.sharding.PartitionSpec(),) * 2,
            check_vma=False))(g, e)
        np.testing.assert_allclose(np.asarray(g2["w"]),
                                   np.asarray(g["w"]), atol=1e-3)


class TestWatchdog:
    def test_straggler_counted(self):
        wd = StepWatchdog(WatchdogConfig(straggler_factor=3.0,
                                         min_timeout_s=60.0))
        for _ in range(8):
            with wd.step():
                time.sleep(0.005)
        with wd.step():
            time.sleep(0.1)       # 20x median -> straggler
        assert wd.stragglers >= 1

    def test_hang_handler_fires(self):
        fired = []
        wd = StepWatchdog(WatchdogConfig(min_timeout_s=0.05),
                          on_hang=lambda: fired.append(1))
        with wd.step():
            time.sleep(0.15)
        assert fired
