"""Mixture-of-Experts FFN (DeepSeek-style: shared + routed, fine-grained).

Dispatch is capacity-based, *group-local* and sort-free: tokens are split
into G groups (one per data shard at scale — matching expert-parallel system
semantics where capacity and drops are per-shard), each group ranks its
(token, choice) pairs per expert via a stable argsort, scatters into a
``[G, E, C_g, D]`` capacity buffer (G on the ``data`` axis, E on the
``model`` axis), runs the expert GEMMs as one batched einsum, and gathers the
outputs back weighted by router gates.

This avoids the O(S·E·C) one-hot dispatch tensor of Switch/GShard — which is
intractable for 256-expert fine-grained MoE — while staying pure
einsum/scatter (TPU-friendly, differentiable, GSPMD-shardable).  Per-chip
capacity memory is ``E·C_g·D / |model|`` — bounded regardless of global
batch.  A dense one-hot path (`dispatch="onehot"`) is kept as the numerical
oracle in tests (groups=1).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, MoEConfig
from .layers import ashard, mlp, mlp_spec
from .specs import ParamSpec


def moe_spec(cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    m: MoEConfig = cfg.moe
    D, E, F = cfg.d_model, m.num_experts, m.d_expert
    layouts = {
        # (wi logical, wo logical) — see MoEConfig.expert_sharding.
        "fsdp_d": ((("expert", "embed", None)), ("expert", None, "embed")),
        "fsdp_f": ((("expert", None, "mlp_fsdp")), ("expert", "mlp_fsdp", None)),
        "ep2d": ((("expert2d", None, None)), ("expert2d", None, None)),
        # manual a2a EP: one expert per chip when E divides the chip count,
        # else E over `model` with d_model FSDP on `data` (gathered inside).
        "ep_a2a": (
            (("expert2d", None, None), ("expert2d", None, None))
            if E % 256 == 0
            else ((("expert", "embed", None)), ("expert", "mlp_fsdp", None))
        ),
    }
    wi_l, wo_l = (
        layouts[m.expert_sharding][0], layouts[m.expert_sharding][1]
    )
    spec: Dict = {
        "router": ParamSpec(
            (D, E), ("embed", None), init="normal", scale=0.006, dtype=jnp.float32
        ),
        # Fused gate+up per expert.
        "wi": ParamSpec((E, D, 2 * F), wi_l, dtype=dtype),
        "wo": ParamSpec((E, F, D), wo_l, dtype=dtype),
    }
    if m.num_shared:
        spec["shared"] = mlp_spec(D, m.num_shared * F, "swiglu", dtype)
    return spec


def _router_probs(logits: jnp.ndarray, m: MoEConfig) -> jnp.ndarray:
    if m.router == "softmax":      # DeepSeek-V2
        return jax.nn.softmax(logits, axis=-1)
    if m.router == "sigmoid":      # DeepSeek-V3
        return jax.nn.sigmoid(logits)
    raise ValueError(m.router)


def _topk_gates(probs: jnp.ndarray, m: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    gates, idx = jax.lax.top_k(probs, m.top_k)
    if m.router == "sigmoid":      # V3 renormalises among the selected
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, idx


def aux_load_balance_loss(probs: jnp.ndarray, counts: jnp.ndarray, m: MoEConfig):
    """Switch-style load-balance auxiliary: E · <f_e> · <p_e> (per group)."""
    G, S, E = probs.shape
    f = counts.astype(jnp.float32) / (S * m.top_k)         # [G, E]
    p = jnp.mean(probs.astype(jnp.float32), axis=1)        # [G, E]
    return jnp.mean(m.num_experts * jnp.sum(f * p, axis=-1))


def _capacity(tokens_per_group: int, m: MoEConfig) -> int:
    c = int(tokens_per_group * m.top_k * m.capacity_factor / m.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for tiling


# ---------------------------------------------------------------------------
# Grouped scatter dispatch (production path)
# ---------------------------------------------------------------------------
def _scatter_moe(p, xg: jnp.ndarray, m: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """xg: [G, S, D] → (y [G, S, D], aux). Capacity overflow tokens drop."""
    G, S, D = xg.shape
    E, k = m.num_experts, m.top_k
    C = _capacity(S, m)
    garange = jnp.arange(G, dtype=jnp.int32)[:, None]

    logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), p["router"])
    probs = _router_probs(logits, m)
    gates, idx = _topk_gates(probs, m)                      # [G, S, k]

    flat_e = idx.reshape(G, S * k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    ranks = jnp.argsort(order, axis=-1).astype(jnp.int32)   # rank within group
    counts = (
        jnp.zeros((G, E), jnp.int32).at[garange, flat_e].add(1)
    )
    aux = aux_load_balance_loss(probs, counts, m)
    starts = jnp.cumsum(counts, axis=-1) - counts           # exclusive prefix
    pos = ranks - jnp.take_along_axis(starts, flat_e, axis=-1).astype(jnp.int32)
    slot = jnp.where(pos < C, flat_e * C + pos, E * C)      # overflow → dropped

    token_of = jnp.repeat(jnp.arange(S, dtype=jnp.int32), k)  # [S*k]
    gathered = xg[:, token_of]                              # [G, S*k, D]
    xe = (
        jnp.zeros((G, E * C + 1, D), xg.dtype).at[garange, slot].add(gathered)
    )
    exp_axes = (
        (None, "expert2d", None, None)
        if m.expert_sharding == "ep2d"
        else ("batch", "expert", None, None)
    )
    xe = ashard(xe[:, : E * C].reshape(G, E, C, D), exp_axes)

    h = jnp.einsum("gecd,edf->gecf", xe, p["wi"])
    h = ashard(h, exp_axes)
    gate_h, up_h = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate_h) * up_h
    ye = jnp.einsum("gecf,efd->gecd", h, p["wo"])
    ye = ashard(ye, exp_axes).reshape(G, E * C, D)
    ye = jnp.concatenate([ye, jnp.zeros((G, 1, D), ye.dtype)], axis=1)

    picked = jnp.take_along_axis(ye, slot[..., None], axis=1)  # [G, S*k, D]
    picked = picked * gates.reshape(G, S * k, 1).astype(ye.dtype)
    y = jnp.zeros((G, S, D), ye.dtype).at[garange, token_of[None, :]].add(picked)
    return y, aux


# ---------------------------------------------------------------------------
# One-hot dispatch (oracle / tiny configs; groups=1 semantics)
# ---------------------------------------------------------------------------
def _onehot_moe(p, xg: jnp.ndarray, m: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    G, S, D = xg.shape
    assert G == 1, "onehot oracle is ungrouped"
    x2d = xg[0]
    E, k = m.num_experts, m.top_k
    C = _capacity(S, m)

    logits = (x2d.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = _router_probs(logits, m)
    gates, idx = _topk_gates(probs, m)
    counts = jnp.zeros((E,), jnp.int32).at[idx.reshape(-1)].add(1)
    aux = aux_load_balance_loss(probs[None], counts[None], m)

    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)        # [S, k, E]
    flat = onehot.reshape(S * k, E)
    pos = jnp.cumsum(flat, axis=0) - flat                   # exclusive prefix
    pos = jnp.sum(pos * flat, axis=-1).reshape(S, k)
    keep = pos < C
    disp = (
        jax.nn.one_hot(idx, E, dtype=x2d.dtype)[..., None]
        * jax.nn.one_hot(jnp.where(keep, pos, C), C + 1, dtype=x2d.dtype)[..., None, :]
    )[..., :C]                                              # [S, k, E, C]
    dispatch = jnp.sum(disp, axis=1)                        # [S, E, C]
    combine = jnp.sum(disp * gates[..., None, None].astype(x2d.dtype), axis=1)

    xe = jnp.einsum("sec,sd->ecd", dispatch, x2d)
    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"])
    gate_h, up_h = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate_h) * up_h
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    y = jnp.einsum("sec,ecd->sd", combine, ye)
    return y[None], aux


# ---------------------------------------------------------------------------
# Manual expert parallelism (shard_map island): explicit all-to-all dispatch
# ---------------------------------------------------------------------------
def _manual_ep_body(cfg: ModelConfig, ep_axes, fsdp_gather: bool,
                    batch_axes=("data",)):
    """Fully-manual EP body. Per chip: route my token slice to expert owners
    over ``ep_axes`` with one all-to-all, run my experts locally, a2a back,
    combine, then psum the token slices over `model`.

    GSPMD resolves the capacity-buffer einsums by replicating expert weights
    (measured: 26-56 TB/chip/step on deepseek-v3 — §Perf); inside a manual
    region the only fabric traffic is the token a2a (~0.6 GB/chip/layer) and
    the output psum.
    """
    m = cfg.moe
    E, k = m.num_experts, m.top_k
    D = cfg.d_model

    def body(x_loc, router, wi_loc, wo_loc):
        # x_loc: [B_loc, T, D] (replicated over `model`); weights local.
        ep = 1
        for ax in ep_axes:
            ep *= jax.lax.axis_size(ax)
        e_loc = E // ep
        midx = jax.lax.axis_index("model")
        msize = jax.lax.axis_size("model")
        B_loc, T, _ = x_loc.shape
        T_loc = T // msize
        # my token slice (dedup across the replicated model axis)
        x_my = jax.lax.dynamic_slice_in_dim(x_loc, midx * T_loc, T_loc, 1)
        S_loc = B_loc * T_loc
        xs = x_my.reshape(S_loc, D)

        logits = xs.astype(jnp.float32) @ router
        probs = _router_probs(logits, m)
        gates, idx = _topk_gates(probs, m)                 # [S_loc, k]
        counts = jnp.zeros((E,), jnp.int32).at[idx.reshape(-1)].add(1)
        aux = aux_load_balance_loss(probs[None], counts[None], m)

        # slot within (dst chip, local expert): capacity per (src, expert)
        C = max(8, -(-int(S_loc * k * m.capacity_factor / E) // 8) * 8)
        flat_e = idx.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        ranks = jnp.argsort(order).astype(jnp.int32)
        starts = jnp.cumsum(counts) - counts
        pos = ranks - starts[flat_e].astype(jnp.int32)
        slot = jnp.where(pos < C, flat_e * C + pos, E * C)  # [S_loc*k]
        token_of = jnp.repeat(jnp.arange(S_loc, dtype=jnp.int32), k)

        send = jnp.zeros((E * C + 1, D), xs.dtype).at[slot].add(xs[token_of])
        send = send[: E * C].reshape(ep, e_loc * C, D)
        if len(ep_axes) == 1:
            recv = jax.lax.all_to_all(send, ep_axes[0], 0, 0, tiled=False)
        else:
            recv = jax.lax.all_to_all(send, ep_axes, 0, 0, tiled=False)
        # recv: [ep(src), e_loc*C, D] → my experts' tokens from every source
        xe = recv.reshape(ep, e_loc, C, D).transpose(1, 0, 2, 3).reshape(
            e_loc, ep * C, D
        )
        if fsdp_gather:
            wi = jax.lax.all_gather(wi_loc, "data", axis=1, tiled=True)
            wo = jax.lax.all_gather(wo_loc, "data", axis=1, tiled=True)
        else:
            wi, wo = wi_loc, wo_loc
        h = jnp.einsum("ecd,edf->ecf", xe, wi)
        g_h, u_h = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(g_h) * u_h
        ye = jnp.einsum("ecf,efd->ecd", h, wo)             # [e_loc, ep*C, D]
        ye = ye.reshape(e_loc, ep, C, D).transpose(1, 0, 2, 3).reshape(
            ep, e_loc * C, D
        )
        if len(ep_axes) == 1:
            back = jax.lax.all_to_all(ye, ep_axes[0], 0, 0, tiled=False)
        else:
            back = jax.lax.all_to_all(ye, ep_axes, 0, 0, tiled=False)
        back = back.reshape(E * C, D)
        back = jnp.concatenate([back, jnp.zeros((1, D), back.dtype)], 0)
        picked = back[slot] * gates.reshape(-1)[:, None].astype(back.dtype)
        y_my = jnp.zeros((S_loc, D), back.dtype).at[token_of].add(picked)
        # Reassemble the sequence: all-gather the T/|model| slices — half the
        # wire of the zero-fill + psum formulation (§Perf iteration).
        y_full = jax.lax.all_gather(
            y_my.reshape(B_loc, T_loc, D), "model", axis=1, tiled=True
        )
        aux = jax.lax.pmean(aux, batch_axes + ("model",))
        return y_full, aux

    return body


def _manual_ep_moe(p, x: jnp.ndarray, cfg: ModelConfig):
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    E = m.num_experts
    # Fully-manual island over ALL mesh axes (partial-manual shard_map trips
    # XLA partitioner bugs).  A `pod` axis, if present, carries extra batch
    # rows (flat multi-pod mode); the EP group stays within a pod and expert
    # grads psum over `pod` at the island boundary (weights are replicated
    # over `pod` in their specs).
    mesh_axes = tuple(jax.sharding.get_abstract_mesh().axis_names)
    batch_axes = ("pod", "data") if "pod" in mesh_axes else ("data",)
    # EP group: all chips of a pod when E divides data*model (deepseek-v3:
    # one expert per chip, weights never move); else the model axis with
    # weight FSDP on data gathered inside (deepseek-v2: E=160).
    two_d = E % 256 == 0
    ep_axes = ("data", "model") if two_d else ("model",)
    fsdp_gather = not two_d
    wspec = P(("data", "model")) if two_d else P("model", "data")
    body = _manual_ep_body(cfg, ep_axes, fsdp_gather, batch_axes)
    fn = jax.shard_map(
        body,
        in_specs=(P(batch_axes, None, None), P(), wspec, wspec),
        out_specs=(P(batch_axes, None, None), P()),
        axis_names=frozenset(mesh_axes),
        check_vma=False,
    )
    return fn(x, p["router"], p["wi"], p["wo"])


def moe_ffn(
    p, x: jnp.ndarray, cfg: ModelConfig, dispatch: str = "scatter"
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE FFN. x: [B, T, D] → (y [B, T, D], aux scalar)."""
    m = cfg.moe
    B, T, D = x.shape
    S = B * T
    use_island = m.expert_sharding == "ep_a2a" and dispatch == "scatter"
    if use_island:
        # The island slices T over `model` to dedup the replicated batch;
        # decode (T=1) and ragged T fall back to the GSPMD scatter path
        # (small tensors — the expensive case the island exists for is the
        # capacity-buffer einsum at training/prefill scale).
        mesh = jax.sharding.get_abstract_mesh()
        msize = dict(mesh.shape).get("model", 1) if mesh is not None else 1
        if T % max(msize, 1) != 0 or msize <= 1:
            use_island = False
    if use_island:
        y, aux = _manual_ep_moe(p, x, cfg)
    else:
        G = m.groups if (m.groups >= 1 and S % m.groups == 0) else 1
        fn = _scatter_moe if dispatch == "scatter" else _onehot_moe
        xg = x.reshape(G, S // G, D)
        if G > 1:
            xg = ashard(xg, ("batch", None, None))
        yg, aux = fn(p, xg, m)
        y = yg.reshape(B, T, D)
    if m.num_shared:
        y = y + mlp(p["shared"], x, "swiglu")
    return ashard(y, ("batch", None, "embed")), aux
