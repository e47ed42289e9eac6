"""Flash-attention Pallas kernel vs pure-jnp oracle (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import flash_attention, ref
from repro.kernels.flash_attention import flash_attention_fwd


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def make_qkv(seed, B, H, KVH, Sq, Skv, D, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        rand(k1, (B, H, Sq, D), dtype),
        rand(k2, (B, KVH, Skv, D), dtype),
        rand(k3, (B, KVH, Skv, D), dtype),
    )


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


class TestForward:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, causal, dtype):
        q, k, v = make_qkv(0, 2, 4, 2, 128, 128, 64, dtype)
        out = flash_attention_fwd(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
        exp = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32), **tol(dtype)
        )

    def test_sliding_window(self):
        q, k, v = make_qkv(1, 1, 2, 2, 256, 256, 32)
        out = flash_attention_fwd(
            q, k, v, causal=True, window=64, block_q=64, block_k=64, interpret=True
        )
        exp = ref.flash_attention_ref(q, k, v, causal=True, window=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5, atol=2e-5)

    def test_gqa_equals_repeated_mha(self):
        """GQA via index_map == physically repeating the kv heads."""
        q, k, v = make_qkv(2, 1, 8, 2, 64, 64, 32)
        out = flash_attention_fwd(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
        k_rep = jnp.repeat(k, 4, axis=1)
        v_rep = jnp.repeat(v, 4, axis=1)
        exp = flash_attention_fwd(
            q, k_rep, v_rep, causal=True, block_q=32, block_k=32, interpret=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-6)

    def test_window_wider_than_seq_is_noop(self):
        q, k, v = make_qkv(3, 1, 2, 1, 64, 64, 32)
        out = flash_attention_fwd(
            q, k, v, causal=True, window=4096, block_q=32, block_k=32, interpret=True
        )
        exp = flash_attention_fwd(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-6)

    def test_cross_attention_no_mask(self):
        """Sq != Skv, no causal mask (encoder-decoder cross-attention)."""
        q, k, v = make_qkv(4, 2, 4, 4, 64, 128, 32)
        out = flash_attention_fwd(q, k, v, block_q=32, block_k=64, interpret=True)
        exp = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5, atol=2e-5)

    @settings(max_examples=12, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        B=st.sampled_from([1, 2]),
        heads=st.sampled_from([(1, 1), (2, 1), (4, 2), (8, 8)]),
        Sq=st.sampled_from([64, 128, 192]),
        Skv=st.sampled_from([64, 128, 256]),
        D=st.sampled_from([32, 64, 128]),
        causal=st.booleans(),
        blocks=st.sampled_from([(32, 32), (64, 64), (64, 32)]),
    )
    def test_property_sweep(self, seed, B, heads, Sq, Skv, D, causal, blocks):
        H, KVH = heads
        bq, bk = blocks
        if causal and Sq != Skv:
            Skv = Sq  # causal mask defined for square layouts in this kernel
        q, k, v = make_qkv(seed, B, H, KVH, Sq, Skv, D)
        out = flash_attention_fwd(q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True)
        exp = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=3e-5, atol=3e-5)


class TestDispatchAndGrad:
    def test_dispatch_modes_agree(self):
        q, k, v = make_qkv(5, 1, 4, 2, 64, 64, 32)
        o_ref = flash_attention(q, k, v, causal=True, impl="ref")
        o_pal = flash_attention(q, k, v, causal=True, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_pal), rtol=2e-5, atol=2e-5)

    def test_custom_vjp_matches_jax_grad_of_ref(self):
        q, k, v = make_qkv(6, 1, 2, 1, 64, 64, 32)

        def loss_op(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, impl="pallas_interpret") ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(ref.flash_attention_ref(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_op, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)
