"""Pieces every plain reference shares: seeded weights from a layout, float32
matmuls at full precision, the fp8 control's rounding, and RMSNorm.

Nothing here imports the program.  A reference family module (``reference/``)
declares its weights as a tree of :class:`Leaf` in the layout the program
takes them, so the harness can hand the same arrays to both; the harness
checks that layout against the program's own parameter shapes at set-up.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


class Leaf(NamedTuple):
    """One weight: ``mean + std * normal`` in ``dtype``.  ``mean`` is a
    number or a tuple that broadcasts over the last dimension."""

    shape: Tuple[int, ...]
    dtype: str
    std: float = 0.0
    mean: Union[float, Tuple[float, ...]] = 0.0


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} out of range")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def make_weights(layout, seed: int, shardings=None):
    """Every weight of ``layout`` from ``seed``, on the device, in one
    jitted call, in the dtype it is served in."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=is_leaf)

    def gen(key):
        out = []
        for i, lf in enumerate(leaves):
            a = jnp.asarray(lf.mean, F32)
            if lf.std:
                a = a + lf.std * jax.random.normal(
                    jax.random.fold_in(key, i), lf.shape, F32)
            out.append(jnp.broadcast_to(a, lf.shape).astype(lf.dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(gen, out_shardings=shardings)(seed_key(seed))


def layout_shapes(layout):
    """``(shape, dtype name)`` for every leaf, for comparing layouts."""
    return jax.tree.map(lambda lf: (tuple(lf.shape), jnp.dtype(lf.dtype).name),
                        layout, is_leaf=is_leaf)


def fp8(a: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(x: jnp.ndarray, w: jnp.ndarray, quant: bool) -> jnp.ndarray:
    """``x @ w`` in float32 at full precision; with ``quant`` both operands
    are first rounded to fp8 (per row of ``x``, per column of ``w``), which
    is the control's lower precision."""
    x, w = x.astype(F32), w.astype(F32)
    if quant:
        x, w = fp8(x, -1), fp8(w, -2)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


@jax.jit
def embed(table: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    return table[tokens].astype(F32)


_final_norm = jax.jit(rmsnorm)
_matmul = jax.jit(mm, static_argnums=2)


def head_logits(x, scale, w, eps: float, quant: bool, blocks: int):
    """Final RMSNorm and the output head over x [T, D], the head's columns
    in ``blocks`` slices so that no float32 copy of the whole head exists."""
    h = _final_norm(x, scale, eps)
    cols = np.array_split(np.arange(w.shape[1]), blocks)
    return jnp.concatenate(
        [_matmul(h, w[:, c[0]:c[-1] + 1], quant) for c in cols], -1)
