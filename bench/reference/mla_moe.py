"""Plain float32 reference of a decoder with multi-head latent attention
and routed experts (DeepSeek-V2, arXiv:2405.04434), one chip's share of the
experts.

Pre-norm blocks: RMSNorm, latent attention, a residual add, RMSNorm, a
feed-forward, a residual add; then a final RMSNorm and an untied output
head.  No biases.

Latent attention without query compression: queries ``h @ wq`` split per
head into ``qk_nope_head_dim`` and ``qk_rope_head_dim`` dims; the latent
``RMSNorm(h @ w_dkv)`` (``kv_lora_rank`` dims) expanded to per-head keys
(``w_uk``) and values (``w_uv``); one rotary key ``h @ w_kr`` shared by
every head.  Rotary position embedding on the rope dims, rotate-half, with
YaRN's frequencies (``rope_scaling``) and cos and sin scaled by
``mscale / mscale_all_dim``; the softmax scale is
``(nope + rope)^-1/2 · mscale_all_dim-term²``.  Causal softmax attention
over the expanded keys, no cache.

The first ``first_k_dense_replace`` layers have a SwiGLU feed-forward of
width ``intermediate_size``.  The others: a float32 softmax router over
``moe.num_experts`` experts, each token's top ``num_experts_per_tok``
probabilities taken as its gates without renormalising, times
``routed_scaling_factor``; of those choices only the experts this chip
holds (``moe.first_held`` onward, ``n_routed_experts`` of them) count,
each a SwiGLU of width ``moe_intermediate_size`` computed densely over
every token and weighted by the token's gate where it chose it; plus
``n_shared_experts`` shared experts as one SwiGLU of their summed width.
Every gate/up projection holds the gate columns first and the up columns
second.

Departure from the published model, which the configuration file lists
too: DeepSeek's checkpoint pairs interleaved rotary dims, this family (like
the program) pairs the two halves; with random weights that is a fixed
permutation of the rope columns of ``wq`` and ``w_kr``.

The reference runs one sequence at a time and one layer at a time, with
the queries in blocks, so that it fits beside the weights on one chip.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.refops import F32, HIGHEST, Leaf, embed, head_logits, mm, rmsnorm

Q_BLOCK = 512
VOCAB_BLOCKS = 4


class Dims(NamedTuple):
    layers: int
    dense_layers: int
    d: int
    heads: int
    rank: int
    nope: int
    rope: int
    v: int
    ff: int
    ff_expert: int
    shared: int
    routed: int
    first_held: int
    held: int
    top_k: int
    route_scale: float
    vocab: int
    theta: float
    eps: float
    yarn: Tuple[float, ...]  # factor, original positions, beta_fast, beta_slow, mscale, mscale_all_dim


def dims(cfg: dict) -> Dims:
    if cfg["q_lora_rank"] or cfg["scoring_func"] != "softmax" \
            or cfg["norm_topk_prob"] or cfg["topk_method"] != "greedy":
        raise ValueError("mla_moe covers DeepSeek-V2's uncompressed queries "
                         "and greedy softmax routing without renormalising")
    rs = cfg["rope_scaling"]
    moe = cfg["moe"]
    return Dims(
        cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
        cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        cfg["n_shared_experts"], moe["num_experts"], moe.get("first_held", 0),
        cfg["n_routed_experts"], cfg["num_experts_per_tok"],
        float(cfg["routed_scaling_factor"]), cfg["vocab_size"],
        float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]),
        (float(rs["factor"]), float(rs["original_max_position_embeddings"]),
         float(rs["beta_fast"]), float(rs["beta_slow"]), float(rs["mscale"]),
         float(rs["mscale_all_dim"])))


def _attn_layout(m: Dims, dt: str, *lead):
    w = lambda fan_in, *s: Leaf((*lead, *s), dt, fan_in ** -0.5)  # noqa: E731
    return {
        "wq": w(m.d, m.d, m.heads, m.nope + m.rope),
        "w_dkv": w(m.d, m.d, m.rank),
        "kv_norm": {"scale": Leaf((*lead, m.rank), dt, 0.1, 1.0)},
        "w_uk": w(m.rank, m.rank, m.heads, m.nope),
        "w_uv": w(m.rank, m.rank, m.heads, m.v),
        "w_kr": w(m.d, m.d, m.rope),
        "wo": w(m.heads * m.v, m.heads * m.v, m.d),
    }


def _block_layout(m: Dims, dt: str, ffn, *lead):
    norm = Leaf((*lead, m.d), dt, 0.1, 1.0)  # scales near 1
    return {"ln1": {"scale": norm}, "attn": _attn_layout(m, dt, *lead),
            "ln2": {"scale": norm}, "ffn": ffn}


def layout(cfg: dict):
    """Weights as the program takes them: the dense layers unrolled, the
    expert layers stacked on a leading axis.  The router is float32."""
    m = dims(cfg)
    L, D, V, F, Fe = m.layers - m.dense_layers, m.d, m.vocab, m.ff, m.ff_expert
    dt = cfg["dtype"]
    w = lambda fan_in, *s: Leaf(s, dt, fan_in ** -0.5)  # noqa: E731
    dense = [_block_layout(m, dt, {"wi": w(D, D, 2 * F), "wo": w(F, F, D)})
             for _ in range(m.dense_layers)]
    moe = {
        "router": Leaf((L, D, m.routed), "float32", D ** -0.5),
        "wi": w(D, L, m.held, D, 2 * Fe),
        "wo": w(Fe, L, m.held, Fe, D),
        "shared": {"wi": w(D, L, D, 2 * m.shared * Fe),
                   "wo": w(m.shared * Fe, L, m.shared * Fe, D)},
    }
    return {
        "embed": {"table": Leaf((V, D), dt, 1.0)},
        "lead": dense,
        "blocks": {"b0": _block_layout(m, dt, moe, L)},
        "tail": [],
        "final_norm": {"scale": Leaf((D,), dt, 0.1, 1.0)},
        "unembed": {"w": w(D, D, V)},
    }


# --------------------------------------------------------- forward ---
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(m: Dims) -> np.ndarray:
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` frequencies for the
    rope dims: ``yarn_find_correction_range``, then a linear ramp between
    interpolated (÷ factor) and original frequencies."""
    factor, orig, beta_fast, beta_slow, _, _ = m.yarn
    dim = m.rope

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(m.theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / m.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (extra / factor) * (1 - mask) + extra * mask


def softmax_scale(m: Dims) -> float:
    factor, _, _, _, _, mscale_all_dim = m.yarn
    s = _yarn_mscale(factor, mscale_all_dim) if mscale_all_dim else 1.0
    return (m.nope + m.rope) ** -0.5 * s * s


def _rope(x, m: Dims):
    """x [T, heads, rope]; rotate-half with YaRN frequencies."""
    T, _, dim = x.shape
    half = dim // 2
    factor, _, _, _, mscale, mscale_all_dim = m.yarn
    ang = jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(yarn_inv_freq(m), F32)
    c = _yarn_mscale(factor, mscale) / _yarn_mscale(factor, mscale_all_dim)
    cos, sin = (jnp.cos(ang) * c)[:, None, :], (jnp.sin(ang) * c)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(m: Dims, quant: bool, p, h):
    T, H = h.shape[0], m.heads
    q = mm(h, p["wq"].reshape(m.d, -1), quant).reshape(T, H, m.nope + m.rope)
    q_nope, q_pe = q[..., :m.nope], _rope(q[..., m.nope:], m)
    ckv = rmsnorm(mm(h, p["w_dkv"], quant), p["kv_norm"]["scale"], m.eps)
    k_pe = _rope(mm(h, p["w_kr"], quant)[:, None, :], m)[:, 0]
    k_nope = mm(ckv, p["w_uk"].reshape(m.rank, -1), quant).reshape(T, H, m.nope)
    v = mm(ckv, p["w_uv"].reshape(m.rank, -1), quant).reshape(T, H, m.v)
    scale = softmax_scale(m)
    outs = []
    for q0 in range(0, T, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, T)
        s = (jnp.einsum("qhd,shd->hqs", q_nope[q0:q1], k_nope[:q1], precision=HIGHEST)
             + jnp.einsum("qhd,sd->hqs", q_pe[q0:q1], k_pe[:q1], precision=HIGHEST))
        causal = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        pr = jax.nn.softmax(jnp.where(causal, s * scale, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqs,shd->qhd", pr, v[:q1], precision=HIGHEST))
    return mm(jnp.concatenate(outs, 0).reshape(T, H * m.v), p["wo"], quant)


def _swiglu(h, wi, wo, quant: bool):
    gu = mm(h, wi, quant)
    f = gu.shape[-1] // 2
    return mm(jax.nn.silu(gu[:, :f]) * gu[:, f:], wo, quant)


def _moe(m: Dims, quant: bool, p, h):
    """The held experts' gated outputs over every token, plus the shared
    experts."""
    probs = jax.nn.softmax(mm(h, p["router"], quant), axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    held = m.first_held + jnp.arange(m.held)
    weight = jnp.sum(jnp.where(idx[:, :, None] == held, gates[:, :, None], 0.0),
                     axis=1) * m.route_scale                     # [T, held]
    outs = jax.vmap(lambda wi, wo: _swiglu(h, wi, wo, quant))(p["wi"], p["wo"])
    routed = jnp.einsum("te,etd->td", weight, outs, precision=HIGHEST)
    return routed + _swiglu(h, p["shared"]["wi"], p["shared"]["wo"], quant)


def _layer(m: Dims, quant: bool, dense: bool, p, x):
    """One block over one sequence x [T, D] (float32)."""
    x = x + _attention(m, quant, p["attn"], rmsnorm(x, p["ln1"]["scale"], m.eps))
    h = rmsnorm(x, p["ln2"]["scale"], m.eps)
    if dense:
        return x + _swiglu(h, p["ffn"]["wi"], p["ffn"]["wo"], quant)
    return x + _moe(m, quant, p["ffn"], h)


_layer_jit = jax.jit(_layer, static_argnums=(0, 1, 2))
_stacked_jit = jax.jit(
    lambda m, quant, blocks, i, x: _layer(
        m, quant, False, jax.tree.map(lambda a: a[i], blocks["b0"]), x),
    static_argnums=(0, 1))


def logits(cfg: dict, w, seq: np.ndarray, first: int, quant: bool = False):
    """Float32 logits [len(seq) - first, V] at positions ``first`` onward of
    one token sequence, on the device.  ``quant`` runs the fp8 control."""
    m = dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = embed(w["embed"]["table"], jnp.asarray(seq, jnp.int32))
        for p in w["lead"]:
            x = _layer_jit(m, quant, True, p, x)
        for i in range(m.layers - m.dense_layers):
            x = _stacked_jit(m, quant, w["blocks"], i, x)
        return head_logits(x[first:], w["final_norm"]["scale"],
                           w["unembed"]["w"], m.eps, quant, VOCAB_BLOCKS)


# --------------------------------------------------------------- counts ---
def _attn_weights(m: Dims) -> int:
    """Parameters of one latent-attention block, its norm included."""
    return (m.d * m.heads * (m.nope + m.rope) + m.d * (m.rank + m.rope) + m.rank
            + m.rank * m.heads * (m.nope + m.v) + m.heads * m.v * m.d)


def _expert_weights(m: Dims) -> int:
    return 3 * m.d * m.ff_expert


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight but the embedding table (router in float32)."""
    m = dims(cfg)
    moe_layers = m.layers - m.dense_layers
    bf16 = (m.layers * (_attn_weights(m) + 2 * m.d)
            + m.dense_layers * 3 * m.d * m.ff
            + moe_layers * (m.held + m.shared) * _expert_weights(m)
            + m.d + m.d * m.vocab)
    return 2 * bf16 + 4 * moe_layers * m.d * m.routed


def latent_bytes_per_token(cfg: dict) -> int:
    """The latent cache: rank + rope bf16 values per token per layer."""
    m = dims(cfg)
    return 2 * m.layers * (m.rank + m.rope)


def held_rows_per_token(m: Dims) -> float:
    """Expected (token, choice) pairs per token whose expert is held, under
    uniform routing: top_k · held / routed."""
    return m.top_k * m.held / m.routed


def _token_flops(m: Dims) -> Tuple[float, float]:
    """(dense-layer, expert-layer) weight FLOPs per token outside attention
    scores: projections, the feed-forward, the expected held expert rows."""
    proj = 2 * (m.d * m.heads * (m.nope + m.rope) + m.d * (m.rank + m.rope)
                + m.heads * m.v * m.d)
    dense = proj + 6 * m.d * m.ff
    moe = (proj + 2 * m.d * m.routed + 6 * m.d * m.shared * m.ff_expert
           + held_rows_per_token(m) * 6 * m.d * m.ff_expert)
    return dense, moe


def prefill_cost(cfg: dict, batch: int, prompt: int):
    """(FLOPs, bytes) a prefill of ``batch`` prompts of ``prompt`` tokens
    needs: weight matmuls (expert rows as expected under uniform routing),
    the latents expanded to per-head keys and values, causal attention, the
    head at the last position; the weights read once, B*P embedding rows,
    the latent cache written."""
    m = dims(cfg)
    dense, moe = _token_flops(m)
    expand = 2 * m.rank * m.heads * (m.nope + m.v)
    attn = 2 * m.heads * (m.nope + m.rope + m.v) * prompt * (prompt + 1) // 2
    per_layer_tok = prompt * expand + attn
    flops = batch * (m.dense_layers * (prompt * dense) + (m.layers - m.dense_layers)
                     * (prompt * moe) + m.layers * per_layer_tok + 2 * m.d * m.vocab)
    nbytes = (weight_bytes(cfg) + batch * prompt * m.d * 2
              + batch * prompt * latent_bytes_per_token(cfg))
    return flops, nbytes


def decode_cost(cfg: dict, batch: int, ctx: int):
    """(FLOPs, bytes) of one decode step for ``batch`` sequences holding
    ``ctx`` cached tokens, attention absorbed into the latent space (scores
    over rank + rope dims, values reduced over rank) for ``ctx + 1`` keys.

    Bytes: every weight but the embedding table, except that each held
    expert is read only if one of the step's B·top_k choices lands on it;
    under uniform routing each token leaves a given expert out with
    probability (routed − top_k) / routed, so the expected share read is
    1 − ((routed − top_k) / routed)^B (64 experts, top-6: 95.7 % at B 32);
    B embedding rows; the valid latent cache read (ctx + 1 tokens) and the
    new token's written."""
    m = dims(cfg)
    dense, moe = _token_flops(m)
    absorb = 2 * m.heads * m.rank * (m.nope + m.v)
    attn = 2 * m.heads * (2 * m.rank + m.rope) * (ctx + 1)
    flops = batch * (m.dense_layers * dense + (m.layers - m.dense_layers) * moe
                     + m.layers * (absorb + attn) + 2 * m.d * m.vocab)
    hit = 1.0 - ((m.routed - m.top_k) / m.routed) ** batch
    experts = 2 * (m.layers - m.dense_layers) * m.held * _expert_weights(m)
    nbytes = (weight_bytes(cfg) - experts + hit * experts + batch * m.d * 2
              + batch * (ctx + 2) * latent_bytes_per_token(cfg))
    return flops, nbytes
