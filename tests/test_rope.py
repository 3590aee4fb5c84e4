"""Rotary embedding: plain, bit for bit as before YaRN existed, and YaRN
against a direct NumPy transcription of DeepSeek-V2's formulas.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/test_rope.py
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.attention import mla_softmax_scale
from repro.models.layers import rope


def _plain_rope(x, positions, theta):
    """The half-split rotary embedding as written before YaRN was added."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def test_rope_without_scaling_is_unchanged_on_glm_shapes():
    cfg = get_config("glm4-9b")
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (2, 64, cfg.num_heads, cfg.resolved_head_dim),
                          jnp.bfloat16)
    for pos in (jnp.arange(64)[None, :], jnp.full((2, 64), 4101)):
        got = jax.jit(rope, static_argnums=2)(x, pos, cfg.rope_theta)
        want = jax.jit(_plain_rope, static_argnums=2)(x, pos, cfg.rope_theta)
        assert cfg.yarn is None
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


# DeepSeek-V2's modeling_deepseek.py, transcribed into NumPy.
def yarn_find_correction_dim(num_rotations, dim, base, max_pos):
    return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / (
        2 * math.log(base))


def yarn_find_correction_range(low_rot, high_rot, dim, base, max_pos):
    low = math.floor(yarn_find_correction_dim(low_rot, dim, base, max_pos))
    high = math.ceil(yarn_find_correction_dim(high_rot, dim, base, max_pos))
    return max(low, 0), min(high, dim - 1)


def yarn_get_mscale(scale=1, mscale=1):
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_linear_ramp_mask(lo, hi, dim):
    if lo == hi:
        hi += 0.001
    return np.clip((np.arange(dim, dtype=np.float32) - lo) / (hi - lo), 0, 1)


def deepseek_yarn_cos_sin(dim, base, factor, orig, beta_fast, beta_slow,
                          mscale, mscale_all_dim, seq_len):
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    low, high = yarn_find_correction_range(beta_fast, beta_slow, dim, base, orig)
    inv_freq_mask = 1.0 - yarn_linear_ramp_mask(low, high, dim // 2)
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    freqs = np.outer(np.arange(seq_len, dtype=np.float32), inv_freq)
    m = yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor, mscale_all_dim)
    emb = np.concatenate([freqs, freqs], -1)
    return np.cos(emb) * m, np.sin(emb) * m


def test_yarn_rope_matches_deepseek_v2():
    cfg = get_config("deepseek-v2-lite")
    y, d = cfg.yarn, cfg.mla.rope_head_dim
    T = 5000  # past the 4096 original positions
    cos, sin = deepseek_yarn_cos_sin(d, cfg.rope_theta, y.factor,
                                     y.original_max_position_embeddings,
                                     y.beta_fast, y.beta_slow, y.mscale,
                                     y.mscale_all_dim, T)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (T, 3, d)),
                   np.float32)
    half = d // 2
    rotated = np.concatenate([-x[..., half:], x[..., :half]], -1)
    want = x * cos[:, None, :] + rotated * sin[:, None, :]
    got = rope(jnp.asarray(x), jnp.arange(T), cfg.rope_theta, y)
    # float32 angles up to 5000 rad: cos and sin agree to a few ulps of
    # the angle, ~5e-4 absolute at the largest positions.
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-3, rtol=0)
    # The blend is real: the ramp runs between dims 10 and 23 of 32.
    assert yarn_find_correction_range(
        y.beta_fast, y.beta_slow, d, cfg.rope_theta,
        y.original_max_position_embeddings) == (10, 23)


def test_mla_softmax_scale_carries_mscale_squared():
    cfg = get_config("deepseek-v2-lite")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert abs(m * m - 1.590) < 1e-3
    plain = cfg.with_overrides(yarn=None)
    assert mla_softmax_scale(plain) == 1 / math.sqrt(192)
