"""Plain float32 reference of an xLSTM stack (Beck et al., arXiv:2405.04517).

The layers cycle through ``pattern`` (7 mLSTM blocks, then 1 sLSTM block, for
xLSTM[7:1]).  Each block is pre-norm with a residual add.

mLSTM block: up-projection ``u = h W_up`` (width ``proj_factor_m * d``) and
output gate ``o = sigmoid(h W_og)``; per head, ``q, k, v`` from ``u`` (q and
k at half the head width, k scaled by ``1/sqrt(d_qk)``), input and forget
pre-activations ``u W_if + b_if`` (exponential input gate, sigmoid forget
gate).  The matrix memory is computed in the paper's *parallel* form:

    D[t, s] = sum_{r=s+1..t} log f_r + log i_s   (s <= t)
    m_t     = max_s D[t, s]
    C[t, s] = (q_t . k_s) exp(D[t, s] - m_t)
    h_t     = sum_s C[t, s] v_s / max(|sum_s C[t, s]|, exp(-m_t))

which equals the stabilised recurrence the program decodes with.  Then
RMSNorm over the whole up-projected width, times ``o``, and ``W_down``.

sLSTM block: ``w = h W_in`` gives z, i, f, o pre-activations; the recurrent
matrices ``R`` act per head on ``h_{t-1}``; exponential input gate with the
stabiliser ``m``; ``h_t = o * c_t / n_t``; a sequential scan.  Then RMSNorm
and a SwiGLU feed-forward (``proj_factor_s * d`` wide, gate columns first).

Departures from the published model, which the program shares and the
configuration file lists: no causal convolution before q/k or in the sLSTM
block, no learnable skip, RMSNorm over the whole width where the paper
normalises per head, block-diagonal R as full per-head matrices.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.refops import F32, HIGHEST, Leaf, embed, head_logits, mm, rmsnorm

VOCAB_BLOCKS = 2


class Dims(NamedTuple):
    layers: int
    pattern: tuple
    d: int
    heads: int
    inner: int      # mLSTM up-projected width
    dh: int         # mLSTM head width
    dqk: int        # mLSTM q/k head width
    dff: int        # sLSTM feed-forward width
    vocab: int
    eps: float


def dims(cfg: dict) -> Dims:
    d, H = cfg["d_model"], cfg["num_heads"]
    inner = int(cfg["proj_factor_m"] * d)
    dh = inner // H
    return Dims(cfg["num_layers"], tuple(cfg["block_pattern"]), d, H, inner,
                dh, int(cfg["qk_dim_factor"] * dh),
                int(cfg["proj_factor_s"] * d), cfg["vocab_size"],
                float(cfg["norm_eps"]))


def layout(cfg: dict):
    m = dims(cfg)
    n_super = m.layers // len(m.pattern)
    D, H, I, dh, dqk, F, V = m.d, m.heads, m.inner, m.dh, m.dqk, m.dff, m.vocab
    dt = cfg["dtype"]
    n = n_super
    norm = lambda *s: Leaf(s, dt, 0.1, 1.0)  # noqa: E731
    w = lambda fan_in, *s: Leaf(s, dt, fan_in ** -0.5)  # noqa: E731
    # Forget-gate biases spread from 3 to 6 over the heads, as the paper
    # initialises them, so the memory reaches far back.  Input-gate bias -3
    # keeps the stabilised normaliser's floor exp(-m) in play; at bias 0 the
    # denominator |n.q| gets small and the random stack is so ill conditioned
    # that bf16 and float32 part ways with depth (48 layers at width 512 on
    # the CPU: logits 2.9 apart at bias 0, 0.2 at -3).
    gate_bias = tuple([-3.0] * H + list(np.linspace(3.0, 6.0, H)))
    blocks = {}
    for i, kind in enumerate(m.pattern):
        if kind == "mlstm":
            cell = {
                "w_up": w(D, n, D, I), "w_og": w(D, n, D, I),
                "wq": w(dh, n, H, dh, dqk), "wk": w(dh, n, H, dh, dqk),
                "wv": w(dh, n, H, dh, dh),
                "w_if": Leaf((n, I, 2 * H), "float32", 0.02),
                "b_if": Leaf((n, 2 * H), "float32", 0.0, gate_bias),
                "gnorm": {"scale": norm(n, I)},
                "w_down": w(I, n, I, D),
            }
        elif kind == "slstm":
            cell = {
                "w_in": w(D, n, D, 4 * D),
                "r": Leaf((n, 4, H, D // H, D // H), dt, 0.02),
                "gnorm": {"scale": norm(n, D)},
                "ffn_wi": w(D, n, D, 2 * F), "ffn_wo": w(F, n, F, D),
            }
        else:
            raise ValueError(f"unknown block kind {kind!r}")
        blocks[f"b{i}"] = {"ln": {"scale": norm(n, D)}, "cell": cell}
    return {
        "embed": {"table": Leaf((V, D), dt, 1.0)},
        "lead": [],
        "blocks": blocks,
        "tail": [],
        "final_norm": {"scale": norm(D)},
        "unembed": {"w": w(D, D, V)},
    }


def _mlstm(m: Dims, quant: bool, p, x):
    """One mLSTM block over x [T, D]."""
    T = x.shape[0]
    H = m.heads
    h = rmsnorm(x, p["ln"]["scale"], m.eps)
    c = p["cell"]
    u = mm(h, c["w_up"], quant)
    og = jax.nn.sigmoid(mm(h, c["w_og"], quant))
    z = u.reshape(T, H, m.dh)
    q = jnp.einsum("thd,hde->hte", z, c["wq"].astype(F32), precision=HIGHEST)
    k = jnp.einsum("thd,hde->hte", z, c["wk"].astype(F32), precision=HIGHEST)
    k = k / np.sqrt(m.dqk)
    v = jnp.einsum("thd,hde->hte", z, c["wv"].astype(F32), precision=HIGHEST)
    g = mm(u, c["w_if"], False) + c["b_if"].astype(F32)
    log_i, log_f = g[:, :H].T, jax.nn.log_sigmoid(g[:, H:]).T      # [H, T]
    cum = jnp.cumsum(log_f, axis=-1)
    dlog = cum[:, :, None] - cum[:, None, :] + log_i[:, None, :]   # [H, t, s]
    dlog = jnp.where(jnp.tril(jnp.ones((T, T), bool)), dlog, -jnp.inf)
    mx = jnp.max(dlog, axis=-1)                                   # [H, T]
    cmat = jnp.einsum("hte,hse->hts", q, k, precision=HIGHEST) * jnp.exp(
        dlog - mx[..., None])
    num = jnp.einsum("hts,hsd->htd", cmat, v, precision=HIGHEST)
    den = jnp.maximum(jnp.abs(cmat.sum(-1)), jnp.exp(-mx))
    hh = (num / den[..., None]).transpose(1, 0, 2).reshape(T, m.inner)
    hh = rmsnorm(hh, c["gnorm"]["scale"], m.eps) * og
    return x + mm(hh, c["w_down"], quant)


def _slstm(m: Dims, quant: bool, p, x):
    """One sLSTM block over x [T, D]; the cell is a scan over time."""
    D, H = m.d, m.heads
    dh = D // H
    h = rmsnorm(x, p["ln"]["scale"], m.eps)
    c = p["cell"]
    wx = mm(h, c["w_in"], quant)                                    # [T, 4D]
    r = c["r"].astype(F32)                                          # [4,H,dh,dh]

    def step(state, w_t):
        cs, ns, ms, hs = state
        rec = jnp.einsum("hd,ghde->ghe", hs.reshape(H, dh), r,
                         precision=HIGHEST).reshape(4, D)
        zt = jnp.tanh(w_t[:D] + rec[0])
        log_i = w_t[D:2 * D] + rec[1]
        log_f = jax.nn.log_sigmoid(w_t[2 * D:3 * D] + rec[2])
        o = jax.nn.sigmoid(w_t[3 * D:] + rec[3])
        m_new = jnp.maximum(log_f + ms, log_i)
        fd, iw = jnp.exp(log_f + ms - m_new), jnp.exp(log_i - m_new)
        cs, ns = fd * cs + iw * zt, fd * ns + iw
        hs = o * cs / ns
        return (cs, ns, m_new, hs), hs

    zero = jnp.zeros((D,), F32)
    _, hs = jax.lax.scan(step, (zero, zero, jnp.full((D,), -jnp.inf), zero), wx)
    y = rmsnorm(hs, c["gnorm"]["scale"], m.eps)
    gu = mm(y, c["ffn_wi"], quant)
    return x + mm(jax.nn.silu(gu[:, :m.dff]) * gu[:, m.dff:], c["ffn_wo"], quant)


def _block(m: Dims, quant: bool, kind: str, stacked, j, x):
    p = jax.tree.map(lambda a: a[j], stacked)
    return (_mlstm if kind == "mlstm" else _slstm)(m, quant, p, x)


_block_jit = jax.jit(_block, static_argnums=(0, 1, 2))


def logits(cfg: dict, w, seq: np.ndarray, first: int, quant: bool = False):
    """Float32 logits [len(seq) - first, V] at positions ``first`` onward of
    one token sequence, on the device.  ``quant`` runs the fp8 control."""
    m = dims(cfg)
    x = embed(w["embed"]["table"], jnp.asarray(seq, jnp.int32))
    for j in range(m.layers // len(m.pattern)):
        for i, kind in enumerate(m.pattern):
            x = _block_jit(m, quant, kind, w["blocks"][f"b{i}"], j, x)
    return head_logits(x[first:], w["final_norm"]["scale"], w["unembed"]["w"],
                       m.eps, quant, VOCAB_BLOCKS)


# --------------------------------------------------------------- counts ---
def _per_token(m: Dims):
    """(matmul FLOPs, recurrence FLOPs) per token over the whole stack."""
    n_super = m.layers // len(m.pattern)
    mat = rec = 0
    for kind in m.pattern:
        if kind == "mlstm":
            mat += (2 * 2 * m.d * m.inner + 2 * m.heads * m.dh * (2 * m.dqk + m.dh)
                    + 2 * m.inner * 2 * m.heads + 2 * m.inner * m.d)
            # memory update (a multiply-add per entry) and read-out
            rec += 4 * m.heads * m.dqk * m.dh
        else:
            dh = m.d // m.heads
            mat += 2 * m.d * 4 * m.d + 6 * m.d * m.dff
            rec += 2 * 4 * m.heads * dh * dh
    return n_super * mat, n_super * rec


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight but the embedding table."""
    lay = layout(cfg)
    total = 0
    for path, lf in jax.tree_util.tree_flatten_with_path(
            lay, is_leaf=lambda x: isinstance(x, Leaf))[0]:
        if path[0].key != "embed":
            total += int(np.prod(lf.shape)) * jnp.dtype(lf.dtype).itemsize
    return total


def state_bytes(cfg: dict) -> int:
    """Float32 recurrent state of one sequence over the whole stack."""
    m = dims(cfg)
    n_super = m.layers // len(m.pattern)
    per = 0
    for kind in m.pattern:
        if kind == "mlstm":
            per += m.heads * (m.dqk * m.dh + m.dqk + 1)
        else:
            per += 4 * m.d
    return 4 * n_super * per


def prefill_cost(cfg: dict, batch: int, prompt: int):
    """(FLOPs, bytes): matmuls and the recurrence in its linear form for
    every prompt token, the head at the last position; the weights read
    once, B*P embedding rows, the final state written."""
    m = dims(cfg)
    mat, rec = _per_token(m)
    flops = batch * (prompt * (mat + rec) + 2 * m.d * m.vocab)
    nbytes = weight_bytes(cfg) + batch * prompt * m.d * 2 + batch * state_bytes(cfg)
    return flops, nbytes


def decode_cost(cfg: dict, batch: int, ctx: int):
    """(FLOPs, bytes) of one decode step: the weights and B embedding rows
    read once, the state read and written.  Independent of ``ctx``."""
    m = dims(cfg)
    mat, rec = _per_token(m)
    flops = batch * (mat + rec + 2 * m.d * m.vocab)
    nbytes = weight_bytes(cfg) + batch * m.d * 2 + 2 * batch * state_bytes(cfg)
    return flops, nbytes
