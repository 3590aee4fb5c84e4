"""Sharding rules for the production meshes.

Parameters are 2-D sharded: every weight matrix puts its "wide" structured
dim (vocab / heads / mlp / expert) on the ``model`` axis (TP/EP) and its
d_model dim on the ``data`` axis (FSDP / ZeRO-3 — XLA SPMD materialises the
all-gather-on-use + reduce-scatter-on-grad schedule).  Activations shard
batch on ``data`` and the head/mlp/vocab dim on ``model``.  The ``pod`` axis
never appears in parameter specs: parameters are replicated across pods and
reconciled by the cohort schedule (repro.core.cohort), which is the paper's
asymmetric design — the slow fabric only ever carries gradient fragments.

KV caches shard batch on ``data`` and heads on ``model`` (MLA latent caches
have no head dim — batch on ``data`` only).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig
from ..models.attention import kv_model_dim
from ..models.specs import pspec_tree, sharding_tree

__all__ = [
    "PARAM_RULES", "ACT_RULES", "param_pspecs", "param_shardings",
    "batch_pspec", "cache_pspecs",
]

# Logical axis name → mesh axis (parameters).
PARAM_RULES: Dict[str, Optional[str]] = {
    "vocab": "model",
    "heads": "model",
    "mlp": "model",
    "expert": "model",
    "expert2d": ("data", "model"),  # pure EP: one expert per chip at E=256
    "embed": "data",     # FSDP shard of the d_model dim
    "mlp_fsdp": "data",  # FFN dim FSDP (MoE fsdp_f layout)
    "layers": None,      # scanned stack dim stays unsharded
}

# Logical activation axis → mesh axis.
ACT_RULES: Dict[str, Optional[str]] = {
    "batch": "data",
    "heads": "model",
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "expert2d": ("data", "model"),
    # d_model dim of *weights* gathered for lookup (embed table): FSDP shard.
    "embed_fsdp": "data",
}


def fit_pspec(ps: P, shape, mesh: Mesh) -> P:
    """Drop mesh axes whose size does not divide the dim (jit in_shardings
    demand exact divisibility; internal constraints pad, input shardings
    don't).  E.g. hubert's vocab=504 on a 16-way model axis → replicated."""
    out = []
    for i, entry in enumerate(ps):
        if entry is None or i >= len(shape):
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out.append(entry if shape[i] % size == 0 else None)
    return P(*out)


def fitted_shardings(shapes_tree, pspec_tree_, mesh: Mesh):
    """NamedShardings from parallel (ShapeDtypeStruct, PartitionSpec) trees,
    with per-leaf divisibility fitting."""
    return jax.tree.map(
        lambda s, ps: NamedSharding(mesh, fit_pspec(ps, s.shape, mesh)),
        shapes_tree,
        pspec_tree_,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, P)),
    )


def param_pspecs(specs, rules: Optional[Dict] = None):
    return pspec_tree(specs, rules or PARAM_RULES)


def param_shardings(specs, mesh: Mesh, rules: Optional[Dict] = None):
    return sharding_tree(specs, mesh, rules or PARAM_RULES)


def batch_pspec(cfg: ModelConfig, shape: ShapeConfig, batch_axes=("data",)) -> Dict:
    """PartitionSpecs for the input batch dict (batch dim over data axes)."""
    b = P(batch_axes)
    out = {}
    if cfg.frontend == "audio":
        out["embeds"] = b
    elif cfg.frontend == "vision":
        out["embeds"] = b
        out["tokens"] = b
    else:
        out["tokens"] = b
    if shape.kind == "train":
        out["labels"] = b
    if shape.kind == "decode":
        out = {"tokens": b}
    return out


def _cache_leaf_pspec(leaf_shape, batch_axes, model_size: int = 0) -> P:
    """Caches: dim0 = batch → data. Head-ful leaves get model on the head dim.

    KVCache k/v [B, S, K, hd]: ``model`` on K when it divides, else on hd
    (`kv_model_dim`, which decode attention follows too)."""
    if len(leaf_shape) == 4:
        if kv_model_dim(leaf_shape[2], leaf_shape[3], model_size) == 3:
            return P(batch_axes, None, None, "model")
        return P(batch_axes, None, "model", None)
    if len(leaf_shape) == 3 and model_size and leaf_shape[1] >= 1024 \
            and leaf_shape[1] % model_size == 0:
        # MLA latent caches [B, S, r] have no head dim: sequence-shard over
        # `model` (the 61-layer c_kv cache is 16 GB/chip replicated at
        # decode_32k batch 128, 1 GB sharded).
        return P(batch_axes, "model", None)
    if len(leaf_shape) == 0:
        return P()
    return P(batch_axes)


def cache_pspecs(cache_spec, batch_axes=("data",), mesh: Optional[Mesh] = None):
    """Specs for the full cache dict {lead, blocks, tail} from Model.cache."""
    msize = dict(mesh.shape).get("model", 0) if mesh is not None else 0

    def leaf_spec(leaf, stacked: bool):
        shape = leaf.shape[1:] if stacked else leaf.shape
        ps = _cache_leaf_pspec(shape, batch_axes, msize)
        if stacked:
            return P(None, *ps)
        return ps

    out = {}
    out["lead"] = jax.tree.map(
        lambda l: leaf_spec(l, False), cache_spec["lead"],
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    out["tail"] = jax.tree.map(
        lambda l: leaf_spec(l, False), cache_spec["tail"],
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    out["blocks"] = (
        jax.tree.map(
            lambda l: leaf_spec(l, True), cache_spec["blocks"],
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        if cache_spec["blocks"] is not None
        else None
    )
    return out
