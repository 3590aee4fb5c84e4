"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

FLASH_CASES = [
    # B, T, H, K, dk, dv, qb, kb, causal, window, dtype
    (2, 64, 4, 2, 32, 32, 16, 32, True, 0, jnp.float32),
    (1, 96, 8, 8, 64, 64, 32, 32, True, 24, jnp.float32),
    (2, 48, 4, 1, 16, 16, 16, 16, False, 0, jnp.float32),
    (1, 80, 4, 2, 32, 16, 32, 16, True, 0, jnp.bfloat16),  # MLA-style dk!=dv
    (1, 50, 2, 2, 16, 16, 16, 16, True, 0, jnp.float32),   # ragged T
    (3, 32, 6, 3, 8, 8, 32, 32, True, 0, jnp.float32),     # single block
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_matches_ref(case):
    B, T, H, K, dk, dv, qb, kb, causal, window, dt = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, dk), dt)
    k = jax.random.normal(ks[1], (B, T, K, dk), dt)
    v = jax.random.normal(ks[2], (B, T, K, dv), dt)
    out = ops.flash_attention(q, k, v, causal, window, qb, kb, None)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol
    )


def test_flash_attention_grads_match_ref():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 64, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 2, 16), jnp.float32)

    def f_kernel(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, True, 0, 16, 16, None) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(ref.flash_attention_ref(q, k, v, causal=True) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_online_attention_equals_kernel_contract():
    """The XLA online-softmax path (the dry-run implementation) and the
    Pallas kernel implement the same function."""
    from repro.models.attention import online_attention

    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 64, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 64, 2, 32), jnp.float32)
    a = online_attention(q, k, v, causal=True, q_block=16, k_block=32)
    b = ops.flash_attention(q, k, v, True, 0, 16, 32, None)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
