"""Flash attention Pallas kernel vs the pure-jnp oracle (interpret mode),
with a hypothesis sweep over shapes/dtypes per the kernel test policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attn import (flash_attention, flash_attention_ref,
                                      flash_mha, flash_mha_bias)


def _rand(rng, shape, dtype):
    x = rng.normal(0, 1, shape)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("BH,S,d,bq,bk", [
        (4, 256, 64, 128, 128),
        (2, 512, 128, 128, 128),
        (1, 128, 64, 64, 64),
        (3, 384, 128, 128, 64),
    ])
    def test_matches_oracle(self, dtype, BH, S, d, bq, bk):
        rng = np.random.default_rng(BH * S)
        q, k, v = (_rand(rng, (BH, S, d), dtype) for _ in range(3))
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        ref = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=TOL[dtype], rtol=TOL[dtype])

    def test_noncausal(self):
        rng = np.random.default_rng(7)
        q, k, v = (_rand(rng, (2, 256, 64), jnp.float32) for _ in range(3))
        out = flash_attention(q, k, v, causal=False, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("with_bias,with_mask", [
        (True, False), (False, True), (True, True)],
        ids=["bias", "mask", "both"])
    def test_bias_and_key_mask(self, dtype, with_bias, with_mask):
        """ParT's shape: 8 heads of 16 over S = T = 128, one grid step."""
        rng = np.random.default_rng(11)
        BH, S, d = 8, 128, 16
        q, k, v = (_rand(rng, (BH, S, d), dtype) for _ in range(3))
        bias = (_rand(rng, (BH, S, S), jnp.float32) if with_bias
                else None)
        mask = (jnp.asarray(rng.random((BH, S)) < 0.6) if with_mask
                else None)
        out = flash_attention(q, k, v, causal=False, block_b=BH, bias=bias,
                              kv_mask=mask, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=False, bias=bias,
                                  kv_mask=mask)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=TOL[dtype], rtol=TOL[dtype])

    def test_fully_masked_row_is_finite(self):
        """Every key of one head masked: the row averages V, never NaN."""
        rng = np.random.default_rng(12)
        q, k, v = (_rand(rng, (2, 128, 16), jnp.float32) for _ in range(3))
        bias = _rand(rng, (2, 128, 128), jnp.float32)
        mask = jnp.ones((2, 128), bool).at[1].set(False)
        out = np.asarray(flash_attention(q, k, v, causal=False, block_b=2,
                                         bias=bias, kv_mask=mask,
                                         interpret=True))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(
            out[1], np.broadcast_to(np.asarray(v[1]).mean(0), (128, 16)),
            atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("S", [16, 256])
    def test_biased_wrapper(self, S):
        """flash_mha_bias == oracle: one whole tile (16), and two kv blocks
        carried through the online softmax (256)."""
        rng = np.random.default_rng(S)
        B, H, hd = 2, 8, 16
        q, k, v = (_rand(rng, (B, H, S, hd), jnp.float32) for _ in range(3))
        bias = _rand(rng, (B, H, S, S), jnp.float32)
        mask = jnp.asarray(rng.random((B, S)) < 0.7)
        out = flash_mha_bias(q, k, v, bias, mask, interpret=True)
        ref = flash_attention_ref(
            *(t.reshape(B * H, S, hd) for t in (q, k, v)), causal=False,
            bias=bias.reshape(B * H, S, S),
            kv_mask=jnp.repeat(mask, H, axis=0)).reshape(B, H, S, hd)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @settings(max_examples=8, deadline=None)
    @given(B=st.integers(1, 2), S=st.sampled_from([96, 200, 256]),
           H=st.sampled_from([4, 8]), kv=st.sampled_from([1, 2, 4]),
           hd=st.sampled_from([32, 64]))
    def test_gqa_wrapper_property(self, B, S, H, kv, hd):
        """flash_mha == oracle for any (batch, seq, heads, kv-groups)."""
        if H % kv:
            kv = 1
        rng = np.random.default_rng(B * S * H)
        q = _rand(rng, (B, S, H, hd), jnp.float32)
        k = _rand(rng, (B, S, kv, hd), jnp.float32)
        v = _rand(rng, (B, S, kv, hd), jnp.float32)
        out = flash_mha(q, k, v, block_q=64, block_k=64, interpret=True)
        n_rep = H // kv
        kr = jnp.repeat(k, n_rep, axis=2)
        vr = jnp.repeat(v, n_rep, axis=2)
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        kf = kr.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        vf = vr.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
        ref = flash_attention_ref(qf, kf, vf).reshape(
            B, H, S, hd).transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)


class TestChunkedXLAAttention:
    """The model-level q-chunked path must equal the dense path."""

    def test_chunked_equals_dense(self):
        from repro.models import attention as A
        rng = np.random.default_rng(3)
        B, S, H, hd, kv = 2, 256, 4, 32, 2
        q = _rand(rng, (B, S, H, hd), jnp.float32)
        k = _rand(rng, (B, S, kv, hd), jnp.float32)
        v = _rand(rng, (B, S, kv, hd), jnp.float32)
        dense = A._sdpa(q, k, v, A._causal_mask(S, S, None), H // kv)
        chunked = A._sdpa_q_chunked(q, k, v, None, H // kv, 64)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)

    def test_chunked_respects_window(self):
        from repro.models import attention as A
        rng = np.random.default_rng(4)
        B, S, H, hd = 1, 128, 2, 16
        q = _rand(rng, (B, S, H, hd), jnp.float32)
        k = _rand(rng, (B, S, H, hd), jnp.float32)
        v = _rand(rng, (B, S, H, hd), jnp.float32)
        dense = A._sdpa(q, k, v, A._causal_mask(S, S, 32), 1)
        chunked = A._sdpa_q_chunked(q, k, v, 32, 1, 32)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)


class TestInt8KVCache:
    def test_int8_cache_decode_close_to_bf16(self):
        from repro.models import attention as A
        cfg16 = A.AttnConfig(d_model=64, n_heads=4, n_kv=2, head_dim=16)
        cfg8 = A.AttnConfig(d_model=64, n_heads=4, n_kv=2, head_dim=16,
                            cache_dtype="int8")
        p = A.attn_init(jax.random.key(0), cfg16)
        x = _rand(np.random.default_rng(5), (2, 1, 64), jnp.float32)
        c16 = A.init_cache(cfg16, 2, 8)
        c8 = A.init_cache(cfg8, 2, 8)
        assert c8.k.dtype == jnp.int8
        y16, _ = A.decode_step(p, x, c16, cfg16)
        y8, _ = A.decode_step(p, x, c8, cfg8)
        # int8 KV costs a little accuracy, not correctness
        err = float(jnp.max(jnp.abs(y16 - y8)))
        ref = float(jnp.max(jnp.abs(y16))) + 1e-9
        assert err / ref < 0.12, (err, ref)
