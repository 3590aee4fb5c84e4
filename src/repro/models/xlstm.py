"""xLSTM blocks: mLSTM (matrix memory, chunkwise-parallel) and sLSTM (scalar
memory, sequential) — Beck et al., arXiv:2405.04517.

mLSTM recurrence per head (d = head dim, stabiliser m):

    log i_t, log f_t = gate projections (log f via logsigmoid)
    m_t  = max(log f_t + m_{t-1}, log i_t)
    C_t  = e^{log f_t + m_{t-1} - m_t} C_{t-1} + e^{log i_t - m_t} v_t k_t^T
    n_t  = ...same decay... + e^{log i_t - m_t} k_t
    h_t  = (C_t q_t) / max(|n_t . q_t|, e^{-m_t})

Training/prefill uses the *chunkwise* form: intra-chunk quadratic attention
with gate-decay masks + inter-chunk recurrent state carried by a scan over
chunks — O(T·K) memory instead of O(T^2), the same trade the flash kernel
makes for softmax attention.  Decode is the plain one-step recurrence.
sLSTM has a true (non-associative) recurrent dependency through h_{t-1}, so
it is a lax.scan over time in all modes, faithful to the paper.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, XLSTMConfig
from .layers import ashard, rmsnorm, rmsnorm_spec
from .specs import ParamSpec


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------
def mlstm_block_spec(cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    x: XLSTMConfig = cfg.xlstm
    D, H = cfg.d_model, cfg.num_heads
    inner = int(x.proj_factor_m * D)
    dh = inner // H
    dqk = dh // 2  # qk at half width (official qk_dim_factor=0.5)
    return {
        "w_up": ParamSpec((D, inner), ("embed", "mlp"), dtype=dtype),
        "w_og": ParamSpec((D, inner), ("embed", "mlp"), dtype=dtype),
        "wq": ParamSpec((H, dh, dqk), ("heads", None, None), dtype=dtype),
        "wk": ParamSpec((H, dh, dqk), ("heads", None, None), dtype=dtype),
        "wv": ParamSpec((H, dh, dh), ("heads", None, None), dtype=dtype),
        "w_if": ParamSpec((inner, 2 * H), ("mlp", None), init="normal",
                          scale=0.02, dtype=jnp.float32),
        "b_if": ParamSpec((2 * H,), (None,), init="zeros", dtype=jnp.float32),
        "gnorm": rmsnorm_spec(inner, dtype),
        "w_down": ParamSpec((inner, D), ("mlp", "embed"), dtype=dtype),
    }


class MLSTMState(NamedTuple):
    c: jnp.ndarray   # [B, H, dqk, dh]
    n: jnp.ndarray   # [B, H, dqk]
    m: jnp.ndarray   # [B, H]


def mlstm_state_spec(cfg: ModelConfig, batch: int) -> MLSTMState:
    x = cfg.xlstm
    H = cfg.num_heads
    inner = int(x.proj_factor_m * cfg.d_model)
    dh = inner // H
    dqk = dh // 2
    return MLSTMState(
        c=jax.ShapeDtypeStruct((batch, H, dqk, dh), jnp.float32),
        n=jax.ShapeDtypeStruct((batch, H, dqk), jnp.float32),
        m=jax.ShapeDtypeStruct((batch, H), jnp.float32),
    )


def _mlstm_qkv_gates(p, x2: jnp.ndarray, cfg: ModelConfig):
    """x2: [B, T, inner] → q,k,v [B,T,H,*], log_i/log_f [B,T,H] (fp32)."""
    H = cfg.num_heads
    B, T, inner = x2.shape
    dh = inner // H
    z = x2.reshape(B, T, H, dh)
    q = jnp.einsum("bthd,hde->bthe", z, p["wq"])
    k = jnp.einsum("bthd,hde->bthe", z, p["wk"]) / math.sqrt(p["wq"].shape[-1])
    v = jnp.einsum("bthd,hde->bthe", z, p["wv"])
    gif = x2.astype(jnp.float32) @ p["w_if"] + p["b_if"]
    log_i, raw_f = jnp.split(gif, 2, axis=-1)             # [B, T, H]
    log_f = jax.nn.log_sigmoid(raw_f)
    return q, k, v, log_i, log_f


def mlstm_chunkwise(
    q, k, v, log_i, log_f, state: MLSTMState, chunk: int
) -> Tuple[jnp.ndarray, MLSTMState]:
    """Chunkwise-parallel mLSTM. Shapes: q,k [B,T,H,dqk], v [B,T,H,dh]."""
    B, T, H, dqk = q.shape
    dh = v.shape[-1]
    K = min(chunk, T)
    pad = (-T) % K
    if pad:
        zp = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v = zp(q), zp(k), zp(v)
        log_i = jnp.pad(log_i, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30)
        log_f = jnp.pad(log_f, ((0, 0), (0, pad), (0, 0)))
    nC = q.shape[1] // K

    # [nC, B, H, K, *]
    rs = lambda a, d: a.reshape(B, nC, K, H, d).transpose(1, 0, 3, 2, 4)
    qcb = rs(q, dqk)
    kc = rs(k, dqk)
    vc = rs(v, dh)
    li = log_i.reshape(B, nC, K, H).transpose(1, 0, 3, 2).astype(jnp.float32)
    lf = log_f.reshape(B, nC, K, H).transpose(1, 0, 3, 2).astype(jnp.float32)

    def chunk_step(carry, xs):
        C, n, m = carry                      # [B,H,dqk,dh], [B,H,dqk], [B,H]
        qb, kb, vb, lib, lfb = xs            # [B,H,K,*]
        G = jnp.cumsum(lfb, axis=-1)         # within-chunk cumulative log f
        # A[t,s] = G_t - G_s + log i_s  for s <= t
        A = G[..., :, None] - G[..., None, :] + lib[..., None, :]
        tri = jnp.tril(jnp.ones((K, K), bool))
        A = jnp.where(tri, A, -jnp.inf)
        m_intra = jnp.max(A, axis=-1)                          # [B,H,K]
        m_t = jnp.maximum(G + m[..., None], m_intra)           # [B,H,K]
        # intra: stabilised decay-weighted attention
        S = jnp.exp(A - m_t[..., None])                        # [B,H,K,K]
        qk = jnp.einsum("bhte,bhse->bhts", qb, kb,
                        preferred_element_type=jnp.float32)
        W = S * qk
        num_intra = jnp.einsum("bhts,bhsd->bhtd", W.astype(vb.dtype), vb)
        den_intra = jnp.sum(W, axis=-1)                        # [B,H,K]
        # inter: contribution of the carried state
        scale = jnp.exp(G + m[..., None] - m_t)                # [B,H,K]
        num_inter = jnp.einsum("bhte,bhed->bhtd", qb, C.astype(qb.dtype))
        num_inter = num_inter.astype(jnp.float32) * scale[..., None]
        den_inter = jnp.einsum("bhte,bhe->bht", qb, n.astype(qb.dtype)) * scale
        num = num_intra.astype(jnp.float32) + num_inter
        den = den_intra + den_inter.astype(jnp.float32)
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
        # state update to the chunk boundary
        g_last = G[..., -1]                                    # [B,H]
        w_end = G[..., -1:] - G + lib                          # [B,H,K]
        m_new = jnp.maximum(g_last + m, jnp.max(w_end, axis=-1))
        decay = jnp.exp(g_last + m - m_new)
        wi = jnp.exp(w_end - m_new[..., None])                 # [B,H,K]
        C_new = decay[..., None, None] * C + jnp.einsum(
            "bhse,bhsd,bhs->bhed", kb.astype(jnp.float32),
            vb.astype(jnp.float32), wi)
        n_new = decay[..., None] * n + jnp.einsum(
            "bhse,bhs->bhe", kb.astype(jnp.float32), wi)
        return (C_new, n_new, m_new), h

    init = (state.c, state.n, state.m)
    (C, n, m), hs = jax.lax.scan(chunk_step, init, (qcb, kc, vc, li, lf))
    h = hs.transpose(1, 0, 3, 2, 4).reshape(B, nC * K, H, dh)[:, :T]
    return h, MLSTMState(c=C, n=n, m=m)


def mlstm_step(q1, k1, v1, li1, lf1, state: MLSTMState):
    """One-token recurrence. q1,k1 [B,H,dqk], v1 [B,H,dh], li/lf [B,H]."""
    m_new = jnp.maximum(lf1 + state.m, li1)
    fd = jnp.exp(lf1 + state.m - m_new)
    iw = jnp.exp(li1 - m_new)
    C = fd[..., None, None] * state.c + iw[..., None, None] * (
        k1.astype(jnp.float32)[..., :, None] * v1.astype(jnp.float32)[..., None, :]
    )
    n = fd[..., None] * state.n + iw[..., None] * k1.astype(jnp.float32)
    num = jnp.einsum("bhe,bhed->bhd", q1.astype(jnp.float32), C)
    den = jnp.einsum("bhe,bhe->bh", q1.astype(jnp.float32), n)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    return h, MLSTMState(c=C, n=n, m=m_new)


def mlstm_block(p, x: jnp.ndarray, cfg: ModelConfig,
                state: MLSTMState | None = None):
    """Full mLSTM block. x: [B,T,D] → ([B,T,D], state)."""
    B, T, D = x.shape
    x2 = ashard(x @ p["w_up"], ("batch", None, "mlp"))
    og = jax.nn.sigmoid(x @ p["w_og"])
    q, k, v, li, lf = _mlstm_qkv_gates(p, x2, cfg)
    if state is None:
        H = cfg.num_heads
        dqk = q.shape[-1]
        dh = v.shape[-1]
        state = MLSTMState(
            c=jnp.zeros((B, H, dqk, dh), jnp.float32),
            n=jnp.zeros((B, H, dqk), jnp.float32),
            m=jnp.full((B, H), -1e30, jnp.float32),
        )
    h, new_state = mlstm_chunkwise(q, k, v, li, lf, state, cfg.xlstm.chunk)
    h = h.reshape(B, T, -1).astype(x.dtype)
    h = rmsnorm(p["gnorm"], h) * og
    out = h @ p["w_down"]
    return ashard(out, ("batch", None, "embed")), new_state


def mlstm_decode(p, x: jnp.ndarray, cfg: ModelConfig, state: MLSTMState):
    B = x.shape[0]
    x2 = x @ p["w_up"]
    og = jax.nn.sigmoid(x @ p["w_og"])
    q, k, v, li, lf = _mlstm_qkv_gates(p, x2, cfg)
    h, new_state = mlstm_step(q[:, 0], k[:, 0], v[:, 0], li[:, 0], lf[:, 0], state)
    h = h.reshape(B, 1, -1).astype(x.dtype)
    h = rmsnorm(p["gnorm"], h) * og
    return ashard(h @ p["w_down"], ("batch", None, "embed")), new_state


def mlstm_reference(p, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Sequential oracle: scan mlstm_step over time."""
    B, T, D = x.shape
    H = cfg.num_heads
    x2 = x @ p["w_up"]
    og = jax.nn.sigmoid(x @ p["w_og"])
    q, k, v, li, lf = _mlstm_qkv_gates(p, x2, cfg)
    dqk, dh = q.shape[-1], v.shape[-1]
    s0 = MLSTMState(
        c=jnp.zeros((B, H, dqk, dh), jnp.float32),
        n=jnp.zeros((B, H, dqk), jnp.float32),
        m=jnp.full((B, H), -1e30, jnp.float32),
    )

    def step(s, xs):
        qt, kt, vt, lit, lft = xs
        h, s = mlstm_step(qt, kt, vt, lit, lft, s)
        return s, h

    _, hs = jax.lax.scan(
        step, s0,
        (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
         li.swapaxes(0, 1), lf.swapaxes(0, 1)),
    )
    h = hs.swapaxes(0, 1).reshape(B, T, -1).astype(x.dtype)
    h = rmsnorm(p["gnorm"], h) * og
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------
def slstm_block_spec(cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    x: XLSTMConfig = cfg.xlstm
    D, H = cfg.d_model, cfg.num_heads
    dh = D // H
    dff = int(x.proj_factor_s * D)
    return {
        # The sLSTM cell is a true sequential recurrence (h_{t-1} feeds the
        # gates), so per-step tensors are tiny ([B, D]); sharding them over
        # `model` costs a collective per TIME STEP (measured: 24k tiny
        # all-reduces per layer at T=4096).  Cell weights/activations are
        # replicated over `model` instead — the model axis idles through the
        # sequential section and the FFN stays tensor-parallel.
        "w_in": ParamSpec((D, 4 * D), ("embed", None), dtype=dtype),
        "r": ParamSpec((4, H, dh, dh), (None, None, None, None),
                       init="normal", scale=0.02, dtype=dtype),
        "gnorm": rmsnorm_spec(D, dtype),
        "ffn_wi": ParamSpec((D, 2 * dff), ("embed", "mlp"), dtype=dtype),
        "ffn_wo": ParamSpec((dff, D), ("mlp", "embed"), dtype=dtype),
    }


class SLSTMState(NamedTuple):
    c: jnp.ndarray  # [B, D]
    n: jnp.ndarray
    m: jnp.ndarray
    h: jnp.ndarray


def slstm_state_spec(cfg: ModelConfig, batch: int) -> SLSTMState:
    D = cfg.d_model
    sd = jax.ShapeDtypeStruct((batch, D), jnp.float32)
    return SLSTMState(c=sd, n=sd, m=sd, h=sd)


def _slstm_cell(p, wx_t, state: SLSTMState, cfg: ModelConfig):
    """wx_t: [B, 4D] precomputed input projections for one step."""
    B = wx_t.shape[0]
    D = cfg.d_model
    H = cfg.num_heads
    dh = D // H
    hr = state.h.reshape(B, H, dh).astype(p["r"].dtype)
    rec = jnp.einsum("bhd,ghde->gbhe", hr, p["r"]).reshape(4, B, D)
    z_in, i_in, f_in, o_in = jnp.split(wx_t, 4, axis=-1)
    z = jnp.tanh(z_in.astype(jnp.float32) + rec[0].astype(jnp.float32))
    log_i = i_in.astype(jnp.float32) + rec[1].astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(f_in.astype(jnp.float32) + rec[2].astype(jnp.float32))
    o = jax.nn.sigmoid(o_in.astype(jnp.float32) + rec[3].astype(jnp.float32))
    m_new = jnp.maximum(log_f + state.m, log_i)
    fd = jnp.exp(log_f + state.m - m_new)
    iw = jnp.exp(log_i - m_new)
    c = fd * state.c + iw * z
    n = fd * state.n + iw
    h = o * c / jnp.maximum(n, 1.0)
    return SLSTMState(c=c, n=n, m=m_new, h=h)


def _slstm_scan_local(p_r, wx, state, cfg: ModelConfig):
    """The sequential cell scan, pure-local math (runs inside a fully-manual
    shard_map when a mesh is active: per-TIME-STEP tensors are tiny and any
    GSPMD sharding of them costs one collective per step per layer — measured
    3 TB/chip/step of 1 MB all-reduces on the 16×16 mesh)."""
    def step(s, wx_t):
        s = _slstm_cell({"r": p_r}, wx_t, s, cfg)
        return s, s.h

    new_state, hs = jax.lax.scan(step, state, wx.swapaxes(0, 1))
    return hs.swapaxes(0, 1), new_state


def slstm_block(p, x: jnp.ndarray, cfg: ModelConfig,
                state: SLSTMState | None = None):
    """x: [B, T, D] → ([B, T, D], state). Sequential over T (faithful)."""
    from ..models.layers import _ACT_RULES

    B, T, D = x.shape
    if state is None:
        z = jnp.zeros((B, D), jnp.float32)
        state = SLSTMState(c=z, n=z, m=jnp.full((B, D), -1e30, jnp.float32), h=z)
    wx = ashard(x @ p["w_in"], ("batch", None, None))  # [B, T, 4D] repl/model

    if _ACT_RULES:  # distributed: fully-manual island, batch over data(+pod)
        from jax.sharding import PartitionSpec as P

        mesh_axes = tuple(jax.sharding.get_abstract_mesh().axis_names)
        b_axes = ("pod", "data") if "pod" in mesh_axes else ("data",)
        bspec = P(b_axes)
        fn = jax.shard_map(
            lambda r, w, s: _slstm_scan_local(r, w, s, cfg),
            in_specs=(P(), bspec, jax.tree.map(lambda _: bspec, state)),
            out_specs=(bspec, jax.tree.map(lambda _: bspec, state)),
            axis_names=frozenset(mesh_axes),
            check_vma=False,
        )
        hs, new_state = fn(p["r"], wx, state)
    else:
        hs, new_state = _slstm_scan_local(p["r"], wx, state, cfg)
    h = hs.astype(x.dtype)
    h = rmsnorm(p["gnorm"], h)
    # position-wise gated FFN
    f = h @ p["ffn_wi"]
    g, u = jnp.split(f, 2, axis=-1)
    out = (jax.nn.silu(g) * u) @ p["ffn_wo"]
    return ashard(out, ("batch", None, "embed")), new_state


def slstm_decode(p, x: jnp.ndarray, cfg: ModelConfig, state: SLSTMState):
    wx = (x @ p["w_in"])[:, 0]
    new_state = _slstm_cell(p, wx, state, cfg)
    h = rmsnorm(p["gnorm"], new_state.h[:, None].astype(x.dtype))
    f = h @ p["ffn_wi"]
    g, u = jnp.split(f, 2, axis=-1)
    out = (jax.nn.silu(g) * u) @ p["ffn_wo"]
    return ashard(out, ("batch", None, "embed")), new_state
