"""Plain float32 reference of a dense decoder with grouped-query attention.

Pre-norm blocks: RMSNorm, attention with ``num_heads`` query heads over
``num_kv_heads`` key/value heads (query head ``h`` reads key/value head
``h // (num_heads / num_kv_heads)``), rotary position embedding on the whole
head (rotate-half), a residual add, RMSNorm, a SwiGLU feed-forward whose
input projection holds the gate columns first and the up columns second, a
residual add; then a final RMSNorm and an untied output head.  No biases.

Departures from GLM-4-9B as published (hf ``THUDM/glm-4-9b``), which the
configuration file lists too: the published model adds a bias to Q, K and V,
rotates only half of each head, and uses RMSNorm epsilon 1.5625e-07; this
family, like the program, has no bias, rotates the whole head, and uses 1e-6.

The reference runs one sequence at a time and one layer at a time, with the
queries in blocks, so that it fits beside the weights on one chip.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.refops import F32, HIGHEST, Leaf, embed, head_logits, mm, rmsnorm

Q_BLOCK = 512
VOCAB_BLOCKS = 4


class Dims(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    theta: float
    eps: float


def dims(cfg: dict) -> Dims:
    return Dims(cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
                cfg["num_kv_heads"], cfg["head_dim"], cfg["d_ff"],
                cfg["vocab_size"], float(cfg["rope_theta"]), float(cfg["norm_eps"]))


def layout(cfg: dict):
    """Weights as the program takes them: layers stacked on a leading axis."""
    m = dims(cfg)
    L, D, H, K, hd, F, V = (m.layers, m.d, m.heads, m.kv_heads, m.head_dim,
                            m.ff, m.vocab)
    dt = cfg["dtype"]
    norm = lambda *s: Leaf(s, dt, 0.1, 1.0)  # noqa: E731  scales near 1
    w = lambda fan_in, *s: Leaf(s, dt, fan_in ** -0.5)  # noqa: E731
    return {
        "embed": {"table": Leaf((V, D), dt, 1.0)},
        "lead": [],
        "blocks": {"b0": {
            "ln1": {"scale": norm(L, D)},
            "attn": {"wq": w(D, L, D, H * hd), "wk": w(D, L, D, K * hd),
                     "wv": w(D, L, D, K * hd), "wo": w(H * hd, L, H * hd, D)},
            "ln2": {"scale": norm(L, D)},
            "ffn": {"wi": w(D, L, D, 2 * F), "wo": w(F, L, F, D)},
        }},
        "tail": [],
        "final_norm": {"scale": norm(D)},
        "unembed": {"w": w(D, D, V)},
    }


def _rope(x, theta):
    """x [T, heads, hd]; rotate-half with frequencies theta^(-i/half)."""
    T, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _layer(m: Dims, quant: bool, blocks, i, x):
    """One block over one sequence x [T, D] (float32)."""
    p = jax.tree.map(lambda a: a[i], blocks["b0"])
    T = x.shape[0]
    H, K, hd = m.heads, m.kv_heads, m.head_dim
    G = H // K
    h = rmsnorm(x, p["ln1"]["scale"], m.eps)
    q = _rope(mm(h, p["attn"]["wq"], quant).reshape(T, H, hd), m.theta)
    k = _rope(mm(h, p["attn"]["wk"], quant).reshape(T, K, hd), m.theta)
    v = mm(h, p["attn"]["wv"], quant).reshape(T, K, hd)
    q = q.reshape(T, K, G, hd) / np.sqrt(hd)
    outs = []
    for q0 in range(0, T, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, T)
        s = jnp.einsum("qkgd,skd->kgqs", q[q0:q1], k[:q1], precision=HIGHEST)
        causal = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", pr, v[:q1], precision=HIGHEST))
    att = jnp.concatenate(outs, 0).reshape(T, H * hd)
    x = x + mm(att, p["attn"]["wo"], quant)
    h = rmsnorm(x, p["ln2"]["scale"], m.eps)
    gu = mm(h, p["ffn"]["wi"], quant)
    gate, up = gu[:, : m.ff], gu[:, m.ff:]
    return x + mm(jax.nn.silu(gate) * up, p["ffn"]["wo"], quant)


_layer_jit = jax.jit(_layer, static_argnums=(0, 1))


def logits(cfg: dict, w, seq: np.ndarray, first: int, quant: bool = False):
    """Float32 logits [len(seq) - first, V] at positions ``first`` onward of
    one token sequence, on the device.  ``quant`` runs the fp8 control."""
    m = dims(cfg)
    x = embed(w["embed"]["table"], jnp.asarray(seq, jnp.int32))
    for i in range(m.layers):
        x = _layer_jit(m, quant, w["blocks"], i, x)
    return head_logits(x[first:], w["final_norm"]["scale"], w["unembed"]["w"],
                       m.eps, quant, VOCAB_BLOCKS)


# --------------------------------------------------------------- counts ---
def _layer_matmul_flops(m: Dims) -> int:
    """Weight-matmul FLOPs of one block for one token."""
    qkvo = 2 * m.d * (2 * m.heads * m.head_dim + 2 * m.kv_heads * m.head_dim)
    return qkvo + 6 * m.d * m.ff


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight but the embedding table."""
    m = dims(cfg)
    per_layer = (m.d * (2 * m.heads * m.head_dim + 2 * m.kv_heads * m.head_dim)
                 + 3 * m.d * m.ff + 2 * m.d)
    return 2 * (m.layers * per_layer + m.d + m.d * m.vocab)


def kv_bytes_per_token(cfg: dict) -> int:
    m = dims(cfg)
    return 2 * m.layers * 2 * m.kv_heads * m.head_dim


def prefill_cost(cfg: dict, batch: int, prompt: int):
    """(FLOPs, bytes) a prefill of ``batch`` prompts of ``prompt`` tokens
    needs: weight matmuls, causal attention, the head at the last position;
    the weights read once, B*P embedding rows, the cache written."""
    m = dims(cfg)
    attn = 4 * m.heads * m.head_dim * prompt * (prompt + 1) // 2
    flops = batch * (m.layers * (prompt * _layer_matmul_flops(m) + attn)
                     + 2 * m.d * m.vocab)
    nbytes = (weight_bytes(cfg) + batch * prompt * m.d * 2
              + batch * prompt * kv_bytes_per_token(cfg))
    return flops, nbytes


def decode_cost(cfg: dict, batch: int, ctx: int):
    """(FLOPs, bytes) of one decode step for ``batch`` sequences holding
    ``ctx`` cached tokens: the new token attends ``ctx + 1`` keys at
    ``num_kv_heads`` heads; the weights and B embedding rows are read once."""
    m = dims(cfg)
    attn = 4 * m.heads * m.head_dim * (ctx + 1)
    flops = batch * (m.layers * (_layer_matmul_flops(m) + attn)
                     + 2 * m.d * m.vocab)
    nbytes = (weight_bytes(cfg) + batch * m.d * 2
              + batch * (ctx + 2) * kv_bytes_per_token(cfg))
    return flops, nbytes
