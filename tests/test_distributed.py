"""Distribution-layer unit tests: planner sharding rules, accumulation
equivalence, cache batch detection, elastic restore."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ckpt as ckpt_lib
from repro import optim
from repro.configs import get_reduced
from repro.distributed import steps
from repro.distributed.planner import (PlanConfig, _axis_size, _div,
                                       cache_sharding, params_sharding)
from repro.launch.mesh import make_mesh
from repro.models import build

P = jax.sharding.PartitionSpec


@pytest.fixture(scope="module")
def mesh1():
    # AbstractMesh: multi-axis sharding specs without needing real devices
    return jax.sharding.AbstractMesh((4, 4), ("data", "model"))


class TestPlannerRules:
    def test_dense_swiglu_is_col_row_sharded(self, mesh1):
        cfg = get_reduced("qwen3-14b")
        model = build(cfg)
        avals = jax.eval_shape(model.init, jax.random.key(0))
        sh = params_sharding(avals, mesh1)
        flat, _ = jax.tree_util.tree_flatten_with_path(sh)
        specs = {"/".join(str(getattr(q, 'key', q)) for q in path): s.spec
                 for path, s in flat}
        wg = next(v for k, v in specs.items() if k.endswith("mlp/wg"))
        wd = next(v for k, v in specs.items() if k.endswith("mlp/wd"))
        # scan-stacked (G, d, f): COL = (fsdp, tp) on trailing dims
        assert wg[-1] == "model" and wg[-2] == "data", wg
        assert wd[-1] == "data" and wd[-2] == "model", wd

    def test_expert_stack_scoped_to_moe(self, mesh1):
        cfg = get_reduced("mixtral-8x7b")
        model = build(cfg)
        avals = jax.eval_shape(model.init, jax.random.key(0))
        sh = params_sharding(avals, mesh1)
        flat, _ = jax.tree_util.tree_flatten_with_path(sh)
        specs = {"/".join(str(getattr(q, 'key', q)) for q in path): s.spec
                 for path, s in flat}
        moe_wg = next(v for k, v in specs.items() if "moe/wg" in k)
        # reduced mixtral: (G, E=4, d, f) with tp=4 -> E over tp, d over fsdp
        assert moe_wg[-3] == "model" and moe_wg[-2] == "data", moe_wg

    def test_tuple_fsdp_axis(self):
        mesh = jax.sharding.AbstractMesh((2, 4, 4),
                                         ("pod", "data", "model"))
        assert _axis_size(mesh, ("pod", "data")) == 8
        assert _div(64, mesh, ("pod", "data")) == ("pod", "data")
        assert _div(63, mesh, ("pod", "data")) is None

    def test_no_leaf_fully_replicated_among_big_weights(self, mesh1):
        """Every >=2-D weight leaf must match some sharding rule (the G1
        regression: unmatched leaves replicate silently)."""
        for arch in ("qwen3-14b", "recurrentgemma-2b", "xlstm-350m",
                     "whisper-base"):
            cfg = get_reduced(arch)
            model = build(cfg)
            avals = jax.eval_shape(model.init, jax.random.key(0))
            sh = params_sharding(avals, mesh1)
            flat_a, _ = jax.tree_util.tree_flatten_with_path(avals)
            flat_s, _ = jax.tree_util.tree_flatten_with_path(sh)
            for (path, a), (_, s) in zip(flat_a, flat_s):
                key = "/".join(str(getattr(q, 'key', q)) for q in path)
                if a.ndim >= 2 and min(a.shape[-2:]) >= 8 \
                        and "norm" not in key and "pos" not in key \
                        and "conv" not in key:
                    assert any(ax is not None for ax in s.spec), \
                        f"{arch}: {key} {a.shape} replicated"


class TestCacheSharding:
    def test_batch_hint_overrides_group_dim(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        cache = {"k": jax.ShapeDtypeStruct((16, 4, 32, 2, 8), jnp.bfloat16)}
        sh = cache_sharding(cache, mesh, batch_size=4)
        # dim0=16 (groups, divisible) must NOT be picked; dim1=4 is batch
        spec = sh["k"].spec
        assert spec[0] is None


class TestAccumEquivalence:
    def test_accum_matches_full_batch(self):
        """Gradient accumulation must be numerically equivalent (same math,
        microbatch means) to the single-shot step."""
        cfg = get_reduced("granite-8b")
        model = build(cfg)
        ocfg = optim.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=4,
                                 clip_norm=None)
        f1 = jax.jit(steps.make_train_step(cfg, ocfg, accum=1))
        f2 = jax.jit(steps.make_train_step(cfg, ocfg, accum=2))
        params = model.init(jax.random.key(0))
        opt = optim.init(params)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)),
                                       jnp.int32)}
        p1, _, m1 = f1(params, opt, batch)
        p2, _, m2 = f2(params, opt, batch)
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]),
                                                  rel=1e-5)
        # identical math up to float reassociation. Adam's first-step update
        # is sign-like (mhat/sqrt(vhat) ~ +-1), so a reassociation-level
        # gradient flip on a ~zero-gradient element moves a param by up to
        # 2*lr — bound by 2.5*lr absolute, not relative.
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=2.5 * ocfg.lr)


class TestElasticRestore:
    def test_restore_onto_new_sharding(self, tmp_path):
        """Checkpoint saved under one layout restores onto another mesh's
        shardings (elastic re-mesh: device count changed)."""
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        ckpt_lib.save(str(tmp_path), 7, tree)
        mesh = make_mesh((1,), ("data",))
        sh = {"w": jax.sharding.NamedSharding(mesh, P("data", None))}
        restored, step, _ = ckpt_lib.restore(str(tmp_path), tree,
                                             sharding_tree=sh)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        assert restored["w"].sharding.spec == P("data", None)
