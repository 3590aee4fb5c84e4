"""Attention: GQA + MLA, train/prefill (online-softmax, chunked) and decode.

The chunked online-softmax implementation (`online_attention`) is the XLA
path used everywhere on CPU and in the dry-run; on TPU the Pallas flash
kernel (`repro.kernels`) implements the same contract and is swapped in via
``ModelConfig.use_pallas``.  Both are validated against each other and against
the quadratic reference in tests.

Sharding note: train and prefill attention *expand GQA KV heads to the full
head count before the attention einsums* (`_expand_kv`).  When the mesh
splits ``heads`` more ways than there are KV heads, the [K, G] factorisation
of H cannot be expressed as a sharding of either dim, and XLA falls back to
"involuntary full rematerialization" (replicate + reslice) on every reshape —
measured at ~100× the expected ICI traffic on the 16×16 mesh.  Expanding
keeps every tensor sharded on the same ``heads`` axis end-to-end; the repeat
is chip-local.  Decode attention never expands: it attends per KV head, with
that head's G = H/K queries as the rows of each matmul, and puts the
``model`` axis on the dim of q and of its output that shards the cache
(`kv_model_dim`), so the cache is read where it lies.  Against the
expanded form on the 16×16 mesh, that cuts decode_32k's ICI bytes a chip
threefold for glm4-9b and tenfold for llama3-8b.

Decode reads a layer's cache and never writes it: the new token's own key
and value join the softmax beside the cache, and the step returns them as
one-slot rows that `write_rows` puts in place after the layer scan, so a
step writes one row a layer rather than passing each layer's whole cache
through the scan.

MLA (DeepSeek multi-head latent attention) keeps the compressed KV cache
``(c_kv, k_rope)`` — 576 floats/token instead of 2·H·d — and uses the
*absorbed-weight* decode path (scores and values computed in the latent
space), which is the memory-roofline win that makes 128-head decode feasible.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import MLAConfig, ModelConfig
from .layers import ashard, rmsnorm, rmsnorm_spec, rope, yarn_mscale
from .specs import ParamSpec

_NEG_INF = -1e30


def _expand_kv(k: jnp.ndarray, H: int) -> jnp.ndarray:
    """[B, T, K, d] → [B, T, H, d] by repeating each KV head H//K times."""
    K = k.shape[2]
    if K == H:
        return k
    reps = H // K
    k = jnp.repeat(k, reps, axis=2)
    return ashard(k, ("batch", None, "heads", None))


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (XLA path; flash-kernel contract)
# ---------------------------------------------------------------------------
def online_attention(
    q: jnp.ndarray,  # [B, Tq, H, dk]
    k: jnp.ndarray,  # [B, Tk, K, dk]
    v: jnp.ndarray,  # [B, Tk, K, dv]
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 512,
    k_block: int = 1024,
    scale: Optional[float] = None,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Blocked attention with running (max, sum) — O(Tq·blk) live memory.

    GQA KV heads are expanded to H.  ``window > 0`` restricts keys to
    ``q_pos - window < k_pos <= q_pos``.  The KV-block scan body is rematted
    (flash-style): backward recomputes the [qb, kb] probability block instead
    of saving nk of them.
    """
    B, Tq, H, dk = q.shape
    _, Tk, K, dv = v.shape
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)

    qb = min(q_block, Tq)
    kb = min(k_block, Tk)
    pq = (-Tq) % qb
    pk = (-Tk) % kb
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // qb, kp.shape[1] // kb

    # [B, nq, H, qb, dk] / [B, nk, H, kb, d*]
    qs = qp.reshape(B, nq, qb, H, dk).transpose(0, 1, 3, 2, 4) * scale
    ks = kp.reshape(B, nk, kb, H, dk).transpose(0, 1, 3, 2, 4)
    vs = vp.reshape(B, nk, kb, H, dv).transpose(0, 1, 3, 2, 4)

    q_pos = q_offset + jnp.arange(nq * qb).reshape(nq, qb)
    k_pos = jnp.arange(nk * kb).reshape(nk, kb)
    k_valid = k_pos < Tk

    def per_batch(qs_b, ks_b, vs_b):
        # qs_b: [nq, H, qb, dk]; ks_b: [nk, H, kb, dk]; vs_b: [nk, H, kb, dv]
        def one_q_block(qi, qpos):
            @jax.checkpoint
            def kv_step(carry, xs):
                m, l, acc = carry
                kb_, vb_, kpos, kval = xs
                s = jnp.einsum(
                    "hqd,hld->hql", qi, kb_, preferred_element_type=jnp.float32
                )
                mask = kval[None, :]
                if causal:
                    mask = mask & (kpos[None, :] <= qpos[:, None])
                if window > 0:
                    mask = mask & (kpos[None, :] > qpos[:, None] - window)
                s = jnp.where(mask[None, :, :], s, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "hql,hld->hqd", p.astype(vb_.dtype), vb_,
                    preferred_element_type=jnp.float32,
                )
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((H, qb), _NEG_INF, jnp.float32)
            l0 = jnp.zeros((H, qb), jnp.float32)
            a0 = jnp.zeros((H, qb, dv), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0), (ks_b, vs_b, k_pos, k_valid)
            )
            return acc / jnp.maximum(l, 1e-30)[..., None]

        return jax.vmap(one_q_block)(qs_b, q_pos)

    out = jax.vmap(per_batch)(qs, ks, vs)        # [B, nq, H, qb, dv]
    out = out.transpose(0, 1, 3, 2, 4).reshape(B, nq * qb, H, dv)
    return out[:, :Tq].astype(v.dtype)


def full_attention_reference(
    q, k, v, *, causal=True, window=0, scale=None, q_offset=0
) -> jnp.ndarray:
    """Quadratic reference (tests + tiny shapes). Same contract as above."""
    B, Tq, H, dk = q.shape
    _, Tk, K, dv = v.shape
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    s = jnp.einsum("bqhd,blhd->bhql", q, k, preferred_element_type=jnp.float32) * scale
    q_pos = q_offset + jnp.arange(Tq)
    k_pos = jnp.arange(Tk)
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhql,blhd->bqhd", p.astype(v.dtype), v)
    return out


def decode_attention(
    q: jnp.ndarray,          # [B, 1, H, dk]
    k_cache: jnp.ndarray,    # [B, S, K, dk]
    v_cache: jnp.ndarray,    # [B, S, K, dv]
    valid: jnp.ndarray,      # [B, S] or [S] bool — cache slots to read
    k_new: jnp.ndarray,      # [B, 1, K, dk]
    v_new: jnp.ndarray,      # [B, 1, K, dv]
    *,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """One query row per sequence against its ``valid`` cache slots and its
    own token's key and value, which the cache does not hold yet, in one
    softmax; per KV head: each head's G = H/K query heads are the rows of
    the score and value matmuls, so the cache is read once, as stored."""
    B, S, K, dk = k_cache.shape
    H = q.shape[2]
    model = jax.sharding.get_abstract_mesh().shape.get("model", 0)
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    valid = jnp.broadcast_to(valid, (B, S))
    qg = ashard(q[:, 0].reshape(B, K, H // K, dk),
                _grouped_axes(kv_model_dim(K, dk, model)))
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
    s_new = jnp.einsum("bkgd,bkd->bkg", qg, k_new[:, 0],
                       preferred_element_type=jnp.float32) * scale
    p, p_new = _softmax_with_new(s, s_new)
    dt = v_cache.dtype
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(dt), v_cache,
                     preferred_element_type=jnp.float32)
    out = out + (p_new.astype(dt).astype(jnp.float32)[..., None]
                 * v_new[:, 0, :, None, :].astype(jnp.float32))
    out = ashard(out, _grouped_axes(kv_model_dim(K, v_cache.shape[-1], model)))
    return out.reshape(B, 1, H, -1).astype(dt)


def _softmax_with_new(s: jnp.ndarray, s_new: jnp.ndarray):
    """Softmax over one row of S + 1 scores held apart: the cache's ``s``
    [..., S] and the new token's ``s_new`` [...] share one max and one
    denominator.  Returns both parts' probabilities in f32."""
    m = jnp.maximum(jnp.max(s, axis=-1), s_new)
    p = jnp.exp(s - m[..., None])
    p_new = jnp.exp(s_new - m)
    denom = jnp.sum(p, axis=-1) + p_new
    return p / denom[..., None], p_new / denom


def decode_slot(cfg: ModelConfig, pos: jnp.ndarray, S: int) -> jnp.ndarray:
    """The cache slot that the token at ``pos`` takes: ``pos % S`` in a
    windowed ring buffer, else ``pos``, held at the last slot once the
    cache is full."""
    return pos % S if cfg.window > 0 else jnp.minimum(pos, S - 1)


def cached_slots(cfg: ModelConfig, pos: jnp.ndarray, S: int) -> jnp.ndarray:
    """[S] bool: the cache slots that the token at ``pos`` attends besides
    itself — those written before it, less the one its own row replaces."""
    i = jnp.arange(S)
    return (i < jnp.minimum(pos, S)) & (i != decode_slot(cfg, pos, S))


def write_rows(cfg: ModelConfig, cache, rows):
    """A decode step's ``rows`` (what `gqa_decode` and `mla_decode` return:
    the new token's entries with one slot, ``length`` advanced) written
    into ``cache`` at the token's slot, one update per array.  A stacked
    cache ([L, B, S, ...], ``length`` [L]) takes all L layers' rows at
    once; every layer holds the same length."""
    axis = cache.length.ndim + 1
    pos = cache.length.reshape(-1)[0]
    slot = decode_slot(cfg, pos, cache[0].shape[axis])
    arrays = [jax.lax.dynamic_update_slice_in_dim(c, r, slot, axis)
              for c, r in zip(cache[:-1], rows[:-1])]
    return type(cache)(*arrays, rows.length)


def kv_model_dim(num_kv_heads: int, head_dim: int, model_size: int) -> int:
    """The dim of a [B, S, K, hd] KV cache that the ``model`` axis shards: K
    where it divides, else hd — GQA models with K < |model| would otherwise
    replicate the whole cache across the model axis (measured 34 GB/chip on
    llama3-8b decode_32k vs 2.2 GB sharded)."""
    if model_size and num_kv_heads % model_size != 0 \
            and head_dim % model_size == 0:
        return 3
    return 2


def _grouped_axes(model_dim: int):
    """Logical axes of a [B, K, G, d] decode operand whose ``model`` axis
    lies where the cache's does (dim 2 of the cache is K, dim 3 is d)."""
    return ("batch", "heads", None, None) if model_dim == 2 \
        else ("batch", None, None, "heads")


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------
def gqa_spec(cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "wq": ParamSpec((D, H * hd), ("embed", "heads"), dtype=dtype),
        "wk": ParamSpec((D, K * hd), ("embed", "heads"), dtype=dtype),
        "wv": ParamSpec((D, K * hd), ("embed", "heads"), dtype=dtype),
        "wo": ParamSpec((H * hd, D), ("heads", "embed"), dtype=dtype),
    }


class KVCache(NamedTuple):
    k: jnp.ndarray       # [B, S, K, hd]
    v: jnp.ndarray
    length: jnp.ndarray  # [] int32 — tokens currently cached


def gqa_cache_spec(cfg: ModelConfig, batch: int, max_len: int, dtype) -> KVCache:
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    S = min(max_len, cfg.window) if cfg.window else max_len
    return KVCache(
        k=jax.ShapeDtypeStruct((batch, S, K, hd), dtype),
        v=jax.ShapeDtypeStruct((batch, S, K, hd), dtype),
        length=jax.ShapeDtypeStruct((), jnp.int32),
    )


def _project_qkv(p, x, cfg: ModelConfig, positions):
    B, T, D = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, T, H, hd)
    k = (x @ p["wk"]).reshape(B, T, K, hd)
    v = (x @ p["wv"]).reshape(B, T, K, hd)
    q = ashard(rope(q, positions, cfg.rope_theta, cfg.yarn),
               ("batch", None, "heads", None))
    k = rope(k, positions, cfg.rope_theta, cfg.yarn)
    return q, k, v


def gqa_attention(p, x, cfg: ModelConfig, *, use_pallas: bool = False):
    """Training/prefill self-attention. x: [B, T, D] → [B, T, D]."""
    B, T, _ = x.shape
    positions = jnp.arange(T)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions)
    if use_pallas:
        from ..kernels import ops as kops

        out = kops.flash_attention(
            q, k, v, causal=cfg.causal, window=cfg.window,
            q_block=cfg.q_block, k_block=cfg.k_block,
        )
    else:
        out = online_attention(
            q, k, v, causal=cfg.causal, window=cfg.window,
            q_block=cfg.q_block, k_block=cfg.k_block,
        )
    out = out.reshape(B, T, -1) @ p["wo"]
    return ashard(out, ("batch", None, "embed"))


def gqa_prefill(p, x, cfg: ModelConfig, max_len: int):
    """Prefill: run attention AND build the cache (ring-buffered if windowed)."""
    B, T, _ = x.shape
    positions = jnp.arange(T)[None, :]
    q, k, v = _project_qkv(p, x, cfg, positions)
    out = online_attention(
        q, k, v, causal=cfg.causal, window=cfg.window,
        q_block=cfg.q_block, k_block=cfg.k_block,
    )
    S = min(max_len, cfg.window) if cfg.window else max_len
    if T >= S:
        ck, cv = k[:, T - S :], v[:, T - S :]
        if cfg.window > 0:
            # Ring-buffer layout: token t lives at slot t % S so decode's
            # ``pos % S`` overwrite hits the oldest entry.
            ck = jnp.roll(ck, shift=T % S, axis=1)
            cv = jnp.roll(cv, shift=T % S, axis=1)
    else:
        pad = ((0, 0), (0, S - T), (0, 0), (0, 0))
        ck, cv = jnp.pad(k, pad), jnp.pad(v, pad)
    cache = KVCache(k=ck, v=cv, length=jnp.int32(T))
    y = out.reshape(B, T, -1) @ p["wo"]
    return ashard(y, ("batch", None, "embed")), cache


def gqa_decode(p, x, cfg: ModelConfig, cache: KVCache):
    """One decode step against a cache it only reads. x: [B, 1, D];
    returns ([B, 1, D], the new token's K and V rows as a one-slot
    `KVCache` with ``length + 1``), for `write_rows`."""
    B, _, D = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pos = cache.length  # absolute position of the new token
    q = (x @ p["wq"]).reshape(B, 1, H, hd)
    k = (x @ p["wk"]).reshape(B, 1, K, hd)
    v = (x @ p["wv"]).reshape(B, 1, K, hd)
    ppos = jnp.full((B, 1), pos, jnp.int32)
    q = rope(q, ppos, cfg.rope_theta, cfg.yarn)
    k = rope(k, ppos, cfg.rope_theta, cfg.yarn)
    valid = cached_slots(cfg, pos, cache.k.shape[1])
    out = decode_attention(q, cache.k, cache.v, valid, k, v)
    y = out.reshape(B, 1, -1) @ p["wo"]
    rows = KVCache(k=k, v=v, length=cache.length + 1)
    return ashard(y, ("batch", None, "embed")), rows


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek V2/V3)
# ---------------------------------------------------------------------------
def mla_spec(cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    m: MLAConfig = cfg.mla
    D, H = cfg.d_model, cfg.num_heads
    dn, dr, dv = m.nope_head_dim, m.rope_head_dim, m.v_head_dim
    spec: Dict = {
        "w_dkv": ParamSpec((D, m.kv_lora_rank), ("embed", None), dtype=dtype),
        "kv_norm": rmsnorm_spec(m.kv_lora_rank, dtype),
        "w_uk": ParamSpec((m.kv_lora_rank, H, dn), (None, "heads", None), dtype=dtype),
        "w_uv": ParamSpec((m.kv_lora_rank, H, dv), (None, "heads", None), dtype=dtype),
        "w_kr": ParamSpec((D, dr), ("embed", None), dtype=dtype),
        "wo": ParamSpec((H * dv, D), ("heads", "embed"), dtype=dtype),
    }
    if m.q_lora_rank:
        spec.update(
            w_dq=ParamSpec((D, m.q_lora_rank), ("embed", None), dtype=dtype),
            q_norm=rmsnorm_spec(m.q_lora_rank, dtype),
            w_uq=ParamSpec(
                (m.q_lora_rank, H, dn + dr), (None, "heads", None), dtype=dtype
            ),
        )
    else:
        spec["wq"] = ParamSpec((D, H, dn + dr), ("embed", "heads", None), dtype=dtype)
    return spec


class MLACache(NamedTuple):
    c_kv: jnp.ndarray    # [B, S, kv_lora]
    k_rope: jnp.ndarray  # [B, S, dr]
    length: jnp.ndarray


def mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int, dtype) -> MLACache:
    m = cfg.mla
    return MLACache(
        c_kv=jax.ShapeDtypeStruct((batch, max_len, m.kv_lora_rank), dtype),
        k_rope=jax.ShapeDtypeStruct((batch, max_len, m.rope_head_dim), dtype),
        length=jax.ShapeDtypeStruct((), jnp.int32),
    )


def _mla_q(p, x, cfg: ModelConfig, positions):
    m = cfg.mla
    if m.q_lora_rank:
        cq = rmsnorm(p["q_norm"], x @ p["w_dq"])
        q = jnp.einsum("btr,rhd->bthd", cq, p["w_uq"])
    else:
        q = jnp.einsum("btd,dhe->bthe", x, p["wq"])
    q_nope, q_rope = q[..., : m.nope_head_dim], q[..., m.nope_head_dim :]
    q_rope = rope(q_rope, positions, cfg.rope_theta, cfg.yarn)
    return ashard(q_nope, ("batch", None, "heads", None)), ashard(
        q_rope, ("batch", None, "heads", None)
    )


def _mla_latents(p, x, cfg: ModelConfig, positions):
    c_kv = rmsnorm(p["kv_norm"], x @ p["w_dkv"])            # [B, T, r]
    k_rope = rope((x @ p["w_kr"])[:, :, None, :], positions, cfg.rope_theta,
                  cfg.yarn)[:, :, 0]
    return c_kv, k_rope


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(nope + rope head dims), times YaRN's mscale² where the
    model sets ``mscale_all_dim`` (DeepSeek-V2)."""
    m, y = cfg.mla, cfg.yarn
    scale = 1.0 / math.sqrt(m.nope_head_dim + m.rope_head_dim)
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_attention(p, x, cfg: ModelConfig, *, use_pallas: bool = False):
    """Training/prefill MLA: expand latents to per-head K/V, flash-attend."""
    m = cfg.mla
    B, T, _ = x.shape
    H = cfg.num_heads
    positions = jnp.arange(T)[None, :]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_latents(p, x, cfg, positions)
    k_nope = ashard(jnp.einsum("btr,rhd->bthd", c_kv, p["w_uk"]),
                    ("batch", None, "heads", None))
    v = ashard(jnp.einsum("btr,rhd->bthd", c_kv, p["w_uv"]),
               ("batch", None, "heads", None))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, m.rope_head_dim))],
        axis=-1,
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    attend = online_attention
    if use_pallas:
        from ..kernels import ops as kops

        attend = kops.flash_attention
    out = attend(
        q, k, v, causal=cfg.causal, window=cfg.window,
        q_block=cfg.q_block, k_block=cfg.k_block, scale=mla_softmax_scale(cfg),
    )
    y = out.reshape(B, T, -1) @ p["wo"]
    return ashard(y, ("batch", None, "embed"))


def mla_prefill(p, x, cfg: ModelConfig, max_len: int):
    m = cfg.mla
    B, T, _ = x.shape
    positions = jnp.arange(T)[None, :]
    y = mla_attention(p, x, cfg)
    c_kv, k_rope = _mla_latents(p, x, cfg, positions)
    pad = max_len - T
    cache = MLACache(
        c_kv=jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
        k_rope=jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))),
        length=jnp.int32(T),
    )
    return y, cache


def mla_decode(p, x, cfg: ModelConfig, cache: MLACache):
    """Absorbed-weight decode: score and reduce in the 512-d latent space.

    q_lat = q_nope · W_uk  →  scores = q_lat · c_kv + q_rope · k_rope
    out   = (attn · c_kv) · W_uv — the cache stays compressed end-to-end.
    The cache is only read: the new token's latent row and rope key join
    the softmax beside it, and are returned as a one-slot `MLACache` with
    ``length + 1``, for `write_rows`.
    """
    B = x.shape[0]
    pos = cache.length
    ppos = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, cfg, ppos)
    c_new, kr_new = _mla_latents(p, x, cfg, ppos)
    c_kv, k_rope = cache.c_kv, cache.k_rope

    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bthd,rhd->bthr", q_nope, p["w_uk"])  # absorb W_uk
        s_lat = jnp.einsum("bthr,bsr->bths", q_lat, c_kv)
        s_rope = jnp.einsum("bthd,bsd->bths", q_rope, k_rope)
        s = (s_lat + s_rope).astype(jnp.float32) * mla_softmax_scale(cfg)
        s_new = (jnp.einsum("bthr,btr->bth", q_lat, c_new)
                 + jnp.einsum("bthd,btd->bth", q_rope, kr_new))
        s_new = s_new.astype(jnp.float32) * mla_softmax_scale(cfg)
        valid = cached_slots(cfg, pos, c_kv.shape[1])
        s = jnp.where(valid[None, None, None, :], s, _NEG_INF)
        a, a_new = _softmax_with_new(s, s_new)
        o_lat = jnp.einsum("bths,bsr->bthr", a.astype(x.dtype), c_kv,
                           preferred_element_type=jnp.float32)
        o_lat = o_lat + (a_new.astype(x.dtype).astype(jnp.float32)[..., None]
                         * c_new[:, :, None, :].astype(jnp.float32))
        out = jnp.einsum("bthr,rhd->bthd", o_lat.astype(x.dtype),
                         p["w_uv"])                             # absorb W_uv
    y = out.reshape(B, 1, -1) @ p["wo"]
    rows = MLACache(c_kv=c_new, k_rope=kr_new, length=cache.length + 1)
    return ashard(y, ("batch", None, "embed")), rows
