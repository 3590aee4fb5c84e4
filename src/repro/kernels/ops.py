"""Jit'd public wrappers for the Pallas kernels, with autodiff.

``flash_attention`` runs the Pallas forward kernel and uses a
recompute-based backward (``jax.custom_vjp`` around the jnp oracle's vjp).
That backward materialises the quadratic ``[B, H, T, T]`` scores; a blocked
backward is still to be written.

Kernels compile with Mosaic when the default backend is a TPU and run in
Pallas interpret mode on any other backend (the CPU test suite).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from . import ref
from .flash_attention import flash_attention_fwd


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def flash_attention(
    q, k, v, causal: bool = True, window: int = 0,
    q_block: int = 512, k_block: int = 1024, scale: Optional[float] = None,
):
    """``[B, T, H, d]`` contract of ``models.attention.online_attention``;
    the kernel itself runs head-major."""
    out = flash_attention_fwd(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
        causal=causal, window=window,
        q_block=q_block, k_block=k_block, scale=scale,
        interpret=jax.default_backend() != "tpu",
    )
    return out.swapaxes(1, 2)


def _fa_fwd(q, k, v, causal, window, q_block, k_block, scale):
    out = flash_attention(q, k, v, causal, window, q_block, k_block, scale)
    return out, (q, k, v)


def _fa_bwd(causal, window, q_block, k_block, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention_ref(
            q_, k_, v_, causal=causal, window=window, scale=scale
        ),
        q, k, v,
    )
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
