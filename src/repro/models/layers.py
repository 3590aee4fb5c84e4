"""Shared layers: norms, MLPs, embeddings, RoPE, losses, activation sharding.

All layers are (spec builder, pure function) pairs over explicit param pytrees
— no module framework, so the same code paths serve init, training, the
dry-run's ShapeDtypeStruct lowering, and the Pallas-kernel swap.
"""

from __future__ import annotations

import contextlib
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .specs import ParamSpec

# ---------------------------------------------------------------------------
# Activation sharding: logical rules installed by the launcher/trainer.
# Empty rules (unit tests, CPU examples) make `ashard` a no-op.
# ---------------------------------------------------------------------------
_ACT_RULES: Dict[str, Optional[object]] = {}


@contextlib.contextmanager
def activation_rules(rules: Dict[str, Optional[object]]):
    global _ACT_RULES
    prev = _ACT_RULES
    _ACT_RULES = dict(rules)
    try:
        yield
    finally:
        _ACT_RULES = prev


def ashard(x: jnp.ndarray, logical: Sequence[Optional[str]]) -> jnp.ndarray:
    """Constrain activation sharding by logical axis names (no-op w/o rules)."""
    if not _ACT_RULES:
        return x
    spec = P(*[(_ACT_RULES.get(n) if n else None) for n in logical])
    return jax.lax.with_sharding_constraint(x, spec)


# ------------------------------------------------------------------- norms --
def rmsnorm_spec(d: int, dtype=jnp.bfloat16) -> Dict[str, ParamSpec]:
    return {"scale": ParamSpec((d,), ("embed",), init="ones", dtype=dtype)}


def rmsnorm(p, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    h = x.astype(jnp.float32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    h = h * jax.lax.rsqrt(var + eps)
    return (h * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_spec(d: int, dtype=jnp.bfloat16) -> Dict[str, ParamSpec]:
    return {
        "scale": ParamSpec((d,), ("embed",), init="ones", dtype=dtype),
        "bias": ParamSpec((d,), ("embed",), init="zeros", dtype=dtype),
    }


def layernorm(p, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.var(h, axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + eps)
    return (h * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(
        x.dtype
    )


# -------------------------------------------------------------------- MLPs --
def mlp_spec(d_model: int, d_ff: int, act: str, dtype=jnp.bfloat16) -> Dict:
    if act == "swiglu":
        return {
            # Fused gate+up projection: one matmul, better MXU utilisation.
            "wi": ParamSpec((d_model, 2 * d_ff), ("embed", "mlp"), dtype=dtype),
            "wo": ParamSpec((d_ff, d_model), ("mlp", "embed"), dtype=dtype),
        }
    return {
        "wi": ParamSpec((d_model, d_ff), ("embed", "mlp"), dtype=dtype),
        "wo": ParamSpec((d_ff, d_model), ("mlp", "embed"), dtype=dtype),
    }


def mlp(p, x: jnp.ndarray, act: str) -> jnp.ndarray:
    h = x @ p["wi"]
    h = ashard(h, ("batch", None, "mlp"))
    if act == "swiglu":
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate) * up
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(f"unknown activation {act}")
    out = h @ p["wo"]
    return ashard(out, ("batch", None, "embed"))


# -------------------------------------------------------------- embeddings --
def embed_spec(vocab: int, d_model: int, dtype=jnp.bfloat16) -> Dict:
    return {
        "table": ParamSpec(
            (vocab, d_model), ("vocab", "embed"), init="embed", scale=0.02, dtype=dtype
        )
    }


def embed(p, tokens: jnp.ndarray) -> jnp.ndarray:
    # Gather the vocab shards (model axis) before the lookup: token gathers on
    # a vocab-sharded operand force XLA down a masked-allreduce path that is
    # broken inside manual subgroups, and the gathered table slice is small
    # (V × D/|data| — e.g. 65 MB/chip for llama3).  The d_model dim stays
    # FSDP-sharded over `data`.
    table = ashard(p["table"], (None, "embed_fsdp"))
    out = jnp.take(table, tokens, axis=0)
    return ashard(out, ("batch", None, "embed"))


def unembed_spec(vocab: int, d_model: int, dtype=jnp.bfloat16) -> Dict:
    return {
        "w": ParamSpec(
            (d_model, vocab), ("embed", "vocab"), init="fan_in", dtype=dtype
        )
    }


def unembed(p, x: jnp.ndarray) -> jnp.ndarray:
    logits = x @ p["w"]
    return ashard(logits, ("batch", None, "vocab"))


# -------------------------------------------------------------------- RoPE --
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, 0.1·mscale·ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_freqs(d: int, theta: float, yarn) -> np.ndarray:
    """DeepSeek-V2's YaRN frequencies for a rotary dim ``d``: the original
    frequencies for the fast dims, those divided by ``factor`` for the slow
    ones, and a linear ramp between the correction dims of ``beta_fast``
    and ``beta_slow`` rotations over the original context."""
    def corr_dim(rot):
        return (d * math.log(yarn.original_max_position_embeddings
                             / (rot * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), d - 1)
    extra = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - low)
                   / ((high - low) or 0.001), 0, 1)
    return (extra / yarn.factor * ramp + extra * (1 - ramp)).astype(np.float32)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         yarn=None) -> jnp.ndarray:
    """Rotary embedding, half-split convention; ``yarn`` (a
    ``YaRNConfig``) blends in YaRN's frequencies and scales cos and sin
    by its ``mscale / mscale_all_dim`` ratio.

    x: [..., T, H, d] (d even); positions: broadcastable to [..., T].
    """
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    else:
        freq = jnp.asarray(_yarn_freqs(d, theta, yarn))
    ang = positions.astype(jnp.float32)[..., None] * freq  # [..., T, half]
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ------------------------------------------------------------------- losses --
def softmax_xent(
    logits: jnp.ndarray, labels: jnp.ndarray, mask: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Mean cross-entropy in fp32. logits [..., V], labels int [...].

    The gold logit is extracted with a one-hot contraction rather than a
    gather: gathers on vocab-sharded operands force an all-gather (and crash
    XLA's partitioner inside manual subgroups); the iota-compare contraction
    partitions cleanly over the ``model`` axis.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    V = logits.shape[-1]
    onehot = (labels[..., None] == jnp.arange(V, dtype=labels.dtype)).astype(
        jnp.float32
    )
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def chunked_xent(
    hidden: jnp.ndarray,
    logits_fn,
    labels: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    chunk: int = 1024,
) -> jnp.ndarray:
    """Cross-entropy without materializing [B, T, V] logits.

    Scans over sequence chunks, computing logits per chunk under remat — the
    memory-roofline lever for large-vocab models (recurrentgemma: V=256k).
    ``logits_fn(h_chunk) -> [B, c, V]`` (works for tied or untied heads).
    """
    B, T, D = hidden.shape
    if T % chunk != 0:
        return softmax_xent(logits_fn(hidden), labels, mask)
    n = T // chunk
    h = hidden.reshape(B, n, chunk, D).swapaxes(0, 1)  # [n, B, c, D]
    y = labels.reshape(B, n, chunk).swapaxes(0, 1)
    m = (
        mask.reshape(B, n, chunk).swapaxes(0, 1).astype(jnp.float32)
        if mask is not None
        else jnp.ones((n, B, chunk), jnp.float32)
    )

    @jax.checkpoint
    def body(carry, xs):
        hc, yc, mc = xs
        logits = logits_fn(hc).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        V = logits.shape[-1]
        onehot = (yc[..., None] == jnp.arange(V, dtype=yc.dtype)).astype(jnp.float32)
        gold = jnp.sum(logits * onehot, axis=-1)
        nll = (logz - gold) * mc
        tot, cnt = carry
        return (tot + jnp.sum(nll), cnt + jnp.sum(mc)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (h, y, m))
    return tot / jnp.maximum(cnt, 1.0)
