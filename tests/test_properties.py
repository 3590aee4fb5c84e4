"""Hypothesis property tests on system invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed; skipping property tests")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.asymmetry import (
    allreduce_wire_bytes,
    cohort_vs_flat_dcn_bytes,
    reduce_scatter_wire_bytes,
)
from repro.models.attention import full_attention_reference, online_attention
from repro.optim import adamw_init, adamw_update


@settings(max_examples=25, deadline=None)
@given(
    T=st.integers(8, 64),
    H=st.sampled_from([2, 4]),
    K=st.sampled_from([1, 2]),
    d=st.sampled_from([8, 16]),
    qb=st.sampled_from([8, 16, 64]),
    kb=st.sampled_from([8, 32]),
    causal=st.booleans(),
    seed=st.integers(0, 2**16),
)
def test_online_attention_equals_reference(T, H, K, d, qb, kb, causal, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, T, H, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, T, K, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, T, K, d), jnp.float32)
    a = online_attention(q, k, v, causal=causal, q_block=qb, k_block=kb)
    b = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


@settings(max_examples=20, deadline=None)
@given(
    S=st.integers(4, 64),
    E=st.sampled_from([4, 8]),
    k=st.sampled_from([1, 2]),
    seed=st.integers(0, 2**16),
)
def test_moe_dispatch_conservation(S, E, k, seed):
    """Every token contributes ≤ k expert slots; outputs are finite; the
    scatter path (the capacity path of sharded meshes) equals the one-hot
    oracle whenever capacity suffices, and so does the expert-share layer
    that a one-device mesh takes."""
    import dataclasses

    from repro.configs.base import ModelConfig, MoEConfig
    from repro.models.moe import _onehot_moe, _scatter_moe, moe_ffn, moe_spec
    from repro.models.specs import init_params

    cfg = ModelConfig(
        name="t", family="moe", num_layers=1, d_model=16, num_heads=2,
        num_kv_heads=2, d_ff=16, vocab_size=32,
        moe=MoEConfig(num_experts=E, top_k=k, d_expert=8,
                      capacity_factor=float(2 * k * E), router="softmax"),
    )
    params = init_params(moe_spec(cfg, jnp.float32), jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, S, 16), jnp.float32)
    y1, a1 = _scatter_moe(params, x, cfg.moe)
    y2, a2 = _onehot_moe(params, x, cfg.moe)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    assert bool(jnp.isfinite(a1)) and bool(jnp.all(jnp.isfinite(y1)))
    y3, a3 = moe_ffn(params, x, cfg, dispatch="scatter")
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y2), atol=1e-4)
    assert bool(jnp.isfinite(a3))


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    lr=st.floats(1e-5, 1e-2),
    steps=st.integers(1, 5),
)
def test_adamw_moves_toward_quadratic_minimum(seed, lr, steps):
    key = jax.random.PRNGKey(seed)
    target = jax.random.normal(key, (8,))
    params = {"w": jnp.zeros((8,))}
    state = adamw_init(params)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    l0 = loss(params)
    for _ in range(steps):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(params, g, state, lr,
                                        weight_decay=0.0, grad_clip=0.0)
    assert loss(params) <= l0 + 1e-6


@settings(max_examples=50, deadline=None)
@given(
    bytes_=st.floats(1.0, 1e12),
    pods=st.integers(2, 8),
    chips=st.sampled_from([4, 16, 64, 256]),
)
def test_cohort_always_reduces_dcn_bytes(bytes_, pods, chips):
    """The paper-mapped invariant: the cohort schedule's DCN traffic is the
    flat schedule's divided by the cohort size (the 'local class never
    touches the fabric' effect)."""
    r = cohort_vs_flat_dcn_bytes(bytes_, pods, chips)
    assert r["cohort_dcn_bytes_per_chip"] < r["flat_dcn_bytes_per_chip"]
    n = pods * chips
    expected = (2 * (n - 1) / n * bytes_) / (
        2 * (pods - 1) / pods * bytes_ / chips
    )
    np.testing.assert_allclose(r["reduction"], expected, rtol=1e-6)
    # the reduction is essentially the cohort size
    assert r["reduction"] > 0.9 * chips


@settings(max_examples=30, deadline=None)
@given(st.floats(1e3, 1e9), st.integers(2, 512))
def test_wire_byte_factors(payload, n):
    ar = allreduce_wire_bytes(payload, n)
    rs = reduce_scatter_wire_bytes(payload, n)
    assert np.isclose(ar, 2 * rs)
    assert 0 < ar < 2 * payload


@settings(max_examples=15, deadline=None)
@given(
    T=st.integers(4, 40),
    W=st.sampled_from([4, 8]),
    seed=st.integers(0, 2**16),
)
def test_rglru_associative_scan_equals_sequential(T, W, seed):
    from repro.kernels.ref import rglru_scan_ref
    from repro.models.recurrent import rglru_scan

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, T, W))) * 0.6 + 0.2
    b = jax.random.normal(ks[1], (2, T, W)) * 0.2
    h0 = jax.random.normal(ks[2], (2, W)) * 0.1
    got = rglru_scan(a, b, h0)
    exp = rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-5)
