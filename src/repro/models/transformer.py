"""Model assembly: block patterns → scanned layer stack → LM / encoder heads.

One `Model` class covers all ten assigned architectures.  The per-layer block
kind comes from ``cfg.block_pattern`` (cycled), giving:

* dense / moe transformers      — ("attn",)
* RecurrentGemma hybrid         — ("rec", "rec", "attn")
* xLSTM                         — ("mlstm",)*7 + ("slstm",)
* HuBERT encoder                — ("attn",), causal=False

Layers are grouped into [lead (unrolled) | scanned super-blocks | tail
(unrolled)] so heterogeneous patterns still compile as a single
``lax.scan`` over stacked parameters (small HLO even for 80-layer models),
with per-super-block remat.  MoE models put their leading dense-FFN layers in
``lead``.

Three entry points per model, matching the dry-run cells:
    loss(params, batch)                      — train_*
    prefill(params, batch, max_len)          — prefill_*
    decode_step(params, cache, tokens)       — decode_* / long_*
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..configs.base import ModelConfig
from . import attention as attn
from . import moe as moe_mod
from . import recurrent as rec
from . import xlstm as xl
from .layers import (
    ashard,
    chunked_xent,
    embed,
    embed_spec,
    mlp,
    mlp_spec,
    rmsnorm,
    rmsnorm_spec,
    softmax_xent,
    unembed,
    unembed_spec,
)
from .specs import ParamSpec, init_params, shape_dtype_tree, stack_layer_specs


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Per-kind block specs / apply / cache
# ---------------------------------------------------------------------------
def _block_spec(cfg: ModelConfig, kind: str, dtype) -> Dict:
    if kind in ("attn", "attn_dense"):
        a = attn.mla_spec(cfg, dtype) if cfg.attention == "mla" else attn.gqa_spec(cfg, dtype)
        if cfg.moe is not None and kind == "attn":
            f = moe_mod.moe_spec(cfg, dtype)
        elif cfg.moe is not None:
            f = mlp_spec(cfg.d_model, cfg.moe.dense_d_ff or cfg.d_ff, cfg.act, dtype)
        else:
            f = mlp_spec(cfg.d_model, cfg.d_ff, cfg.act, dtype)
        return {
            "ln1": rmsnorm_spec(cfg.d_model, dtype),
            "attn": a,
            "ln2": rmsnorm_spec(cfg.d_model, dtype),
            "ffn": f,
        }
    if kind == "rec":
        return {
            "ln1": rmsnorm_spec(cfg.d_model, dtype),
            "rec": rec.rglru_block_spec(cfg, dtype),
            "ln2": rmsnorm_spec(cfg.d_model, dtype),
            "ffn": mlp_spec(cfg.d_model, cfg.d_ff, cfg.act, dtype),
        }
    if kind == "mlstm":
        return {"ln": rmsnorm_spec(cfg.d_model, dtype),
                "cell": xl.mlstm_block_spec(cfg, dtype)}
    if kind == "slstm":
        return {"ln": rmsnorm_spec(cfg.d_model, dtype),
                "cell": xl.slstm_block_spec(cfg, dtype)}
    raise ValueError(f"unknown block kind {kind!r}")


def _block_apply(cfg: ModelConfig, kind: str, p, x, mode: str,
                 cache=None, max_len: int = 0):
    """Returns (x, new_cache, aux). mode: train | prefill | decode."""
    aux = jnp.float32(0)
    # Named scopes put "attn" and "ffn" into the ops' metadata, so that a
    # profile maps device ops back to the block's parts.
    if kind in ("attn", "attn_dense"):
        with jax.named_scope("attn"):
            h = rmsnorm(p["ln1"], x)
            if cfg.attention == "mla":
                if mode == "train":
                    y, new_cache = attn.mla_attention(
                        p["attn"], h, cfg, use_pallas=cfg.use_pallas), cache
                elif mode == "prefill":
                    y, new_cache = attn.mla_prefill(p["attn"], h, cfg, max_len)
                else:
                    y, new_cache = attn.mla_decode(p["attn"], h, cfg, cache)
            else:
                if mode == "train":
                    y, new_cache = attn.gqa_attention(
                        p["attn"], h, cfg, use_pallas=cfg.use_pallas), cache
                elif mode == "prefill":
                    y, new_cache = attn.gqa_prefill(p["attn"], h, cfg, max_len)
                else:
                    y, new_cache = attn.gqa_decode(p["attn"], h, cfg, cache)
            x = x + y
        with jax.named_scope("ffn"):
            h = rmsnorm(p["ln2"], x)
            if cfg.moe is not None and kind == "attn":
                y, aux = moe_mod.moe_ffn(p["ffn"], h, cfg)
            else:
                y = mlp(p["ffn"], h, cfg.act)
            return x + y, new_cache, aux
    if kind == "rec":
        h = rmsnorm(p["ln1"], x)
        if mode == "train":
            y = rec.rglru_block(p["rec"], h, cfg)
            new_cache = cache
        elif mode == "prefill":
            y, new_cache = rec.rglru_block_with_state(p["rec"], h, cfg, None)
        else:
            y, new_cache = rec.rglru_decode(p["rec"], h, cfg, cache)
        x = x + y
        with jax.named_scope("ffn"):
            h = rmsnorm(p["ln2"], x)
            return x + mlp(p["ffn"], h, cfg.act), new_cache, aux
    if kind in ("mlstm", "slstm"):
        mod = xl if True else None
        h = rmsnorm(p["ln"], x)
        if kind == "mlstm":
            if mode == "decode":
                y, new_cache = xl.mlstm_decode(p["cell"], h, cfg, cache)
            else:
                y, new_cache = xl.mlstm_block(p["cell"], h, cfg,
                                              None if mode == "train" else None)
                if mode == "train":
                    new_cache = cache
        else:
            if mode == "decode":
                y, new_cache = xl.slstm_decode(p["cell"], h, cfg, cache)
            else:
                y, new_cache = xl.slstm_block(p["cell"], h, cfg, None)
                if mode == "train":
                    new_cache = cache
        return x + y, new_cache, aux
    raise ValueError(kind)


def _block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                 dtype, as_spec: bool):
    """Cache spec (ShapeDtypeStruct) or concrete initial cache per kind."""
    def conc(tree):
        if as_spec:
            return tree
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), tree,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )

    if kind in ("attn", "attn_dense"):
        if cfg.attention == "mla":
            spec = attn.mla_cache_spec(cfg, batch, max_len, dtype)
            return conc(spec)
        spec = attn.gqa_cache_spec(cfg, batch, max_len, dtype)
        return conc(spec)
    if kind == "rec":
        spec = rec.rglru_state_spec(cfg, batch)
        return conc(spec)
    if kind == "mlstm":
        spec = xl.mlstm_state_spec(cfg, batch)
        if as_spec:
            return spec
        c = conc(spec)
        return c._replace(m=jnp.full(c.m.shape, -1e30, jnp.float32))
    if kind == "slstm":
        spec = xl.slstm_state_spec(cfg, batch)
        if as_spec:
            return spec
        c = conc(spec)
        return c._replace(m=jnp.full(c.m.shape, -1e30, jnp.float32))
    raise ValueError(kind)


#: Caches that decode extends by one row a step (`attn.write_rows`); every
#: other cache is a recurrent state that a step replaces whole.
_ROW_CACHES = (attn.KVCache, attn.MLACache)


# ---------------------------------------------------------------------------
# Layer grouping
# ---------------------------------------------------------------------------
class LayerPlan(NamedTuple):
    lead: Tuple[str, ...]       # unrolled leading layers (kinds)
    pattern: Tuple[str, ...]    # scanned super-block pattern
    n_scan: int                 # number of scanned super-blocks
    tail: Tuple[str, ...]       # unrolled trailing layers


def layer_plan(cfg: ModelConfig) -> LayerPlan:
    kinds: List[str] = [
        cfg.block_pattern[i % len(cfg.block_pattern)] for i in range(cfg.num_layers)
    ]
    n_lead = cfg.moe.num_dense_layers if cfg.moe is not None else 0
    lead = tuple("attn_dense" for _ in range(n_lead))
    rest = kinds[n_lead:]
    p = len(cfg.block_pattern)
    n_scan = len(rest) // p
    tail = tuple(rest[n_scan * p :])
    return LayerPlan(lead=lead, pattern=tuple(cfg.block_pattern), n_scan=n_scan,
                     tail=tail)


# ---------------------------------------------------------------------------
# The Model
# ---------------------------------------------------------------------------
class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.plan = layer_plan(cfg)
        self.dtype = _dtype(cfg)

    # ------------------------------------------------------------- specs ---
    def specs(self) -> Dict:
        cfg, plan, dt = self.cfg, self.plan, self.dtype
        sb_spec = {f"b{i}": _block_spec(cfg, k, dt) for i, k in enumerate(plan.pattern)}
        out: Dict[str, Any] = {
            "embed": embed_spec(cfg.vocab_size, cfg.d_model, dt),
            "lead": [_block_spec(cfg, k, dt) for k in plan.lead],
            "blocks": stack_layer_specs(sb_spec, plan.n_scan) if plan.n_scan else {},
            "tail": [_block_spec(cfg, k, dt) for k in plan.tail],
            "final_norm": rmsnorm_spec(cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            out["unembed"] = unembed_spec(cfg.vocab_size, cfg.d_model, dt)
        if cfg.mtp_depth:
            out["mtp"] = {
                "proj": ParamSpec((2 * cfg.d_model, cfg.d_model),
                                  ("embed", None), dtype=dt),
                "block": _block_spec(cfg, "attn_dense" if cfg.moe else "attn", dt),
                "norm": rmsnorm_spec(cfg.d_model, dt),
            }
        return out

    def init(self, rng) -> Dict:
        return init_params(self.specs(), rng)

    def param_shapes(self) -> Dict:
        return shape_dtype_tree(self.specs())

    # ----------------------------------------------------------- forward ---
    def _logits(self, params, h):
        with jax.named_scope("head"):
            if self.cfg.tie_embeddings:
                return h @ params["embed"]["table"].T
            return unembed(params["unembed"], h)

    def _embed_inputs(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        if cfg.frontend == "audio":
            return batch["embeds"].astype(self.dtype)  # stub frontend output
        x = embed(params["embed"], batch["tokens"])
        if cfg.frontend == "vision":
            x = jnp.concatenate([batch["embeds"].astype(x.dtype), x], axis=1)
        return x

    def _stack(self, params, x, mode, caches=None, max_len: int = 0):
        """Run lead → scanned super-blocks → tail. Returns (x, caches, aux)."""
        cfg, plan = self.cfg, self.plan
        aux_total = jnp.float32(0)
        new_lead = []
        for p_l, kind, c_l in zip(
            params["lead"], plan.lead,
            caches["lead"] if caches else [None] * len(plan.lead),
        ):
            x, nc, aux = _block_apply(cfg, kind, p_l, x, mode, c_l, max_len)
            new_lead.append(nc)
            aux_total = aux_total + aux

        new_scan = caches["blocks"] if caches else None
        if plan.n_scan:
            def superblock(x_and_aux, xs):
                x_, aux_ = x_and_aux
                p_sb, c_sb = xs
                ncs = {}
                for i, kind in enumerate(plan.pattern):
                    c_i = c_sb[f"b{i}"] if c_sb is not None else None
                    x_, nc, a = _block_apply(cfg, kind, p_sb[f"b{i}"], x_, mode,
                                             c_i, max_len)
                    ncs[f"b{i}"] = nc
                    aux_ = aux_ + a
                return (x_, aux_), ncs

            body = superblock
            if cfg.remat != "none" and mode == "train":
                body = jax.checkpoint(superblock, prevent_cse=False)

            c_scan = caches["blocks"] if caches is not None else None
            if c_scan is None:
                # dummy per-layer None caches for scan structure
                (x, aux_total), _ = jax.lax.scan(
                    lambda ca, p_sb: body(ca, (p_sb, None)),
                    (x, aux_total), params["blocks"],
                )
            else:
                (x, aux_total), new_scan = jax.lax.scan(
                    body, (x, aux_total), (params["blocks"], c_scan)
                )

        new_tail = []
        for p_l, kind, c_l in zip(
            params["tail"], plan.tail,
            caches["tail"] if caches else [None] * len(plan.tail),
        ):
            x, nc, aux = _block_apply(cfg, kind, p_l, x, mode, c_l, max_len)
            new_tail.append(nc)
            aux_total = aux_total + aux

        new_caches = (
            {"lead": new_lead, "blocks": new_scan, "tail": new_tail}
            if caches is not None
            else None
        )
        if mode == "decode":
            new_caches = self._write_decode(caches, new_caches)
        return x, new_caches, aux_total

    def _write_decode(self, caches, new):
        """After a decode stack: each KV or latent cache, read-only inside
        the scan, takes its layers' new rows at the step's slot
        (`attn.write_rows`: one update per array, in the donated buffer);
        a recurrent state is replaced whole.  Records ``decode.cache_write``
        with the layers of each kind and the bytes of rows a step writes."""
        counts = dict(row_layers=0, state_layers=0, row_bytes_per_step=0)

        def put(c, n, layers):
            if not isinstance(c, _ROW_CACHES):
                counts["state_layers"] += layers
                return n
            counts["row_layers"] += layers
            counts["row_bytes_per_step"] += sum(a.size * a.dtype.itemsize
                                                for a in n[:-1])
            return attn.write_rows(self.cfg, c, n)

        out = {
            "lead": [put(c, n, 1) for c, n in zip(caches["lead"], new["lead"])],
            "blocks": None if caches["blocks"] is None else {
                k: put(c, new["blocks"][k], self.plan.n_scan)
                for k, c in caches["blocks"].items()},
            "tail": [put(c, n, 1) for c, n in zip(caches["tail"], new["tail"])],
        }
        t = time.perf_counter_ns()
        obs.record("decode.cache_write", t, t, **counts)
        return out

    def forward(self, params, batch) -> jnp.ndarray:
        """Training-mode forward to final hidden states [B, T, D]."""
        x = self._embed_inputs(params, batch)
        x, _, aux = self._stack(params, x, "train")
        return rmsnorm(params["final_norm"], x), aux

    # -------------------------------------------------------------- loss ---
    def loss(self, params, batch) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        h, aux = self.forward(params, batch)
        labels = batch["labels"]
        mask = batch.get("mask")
        if cfg.frontend == "vision":
            h = h[:, cfg.frontend_tokens :]  # loss over text positions only
        T = labels.shape[1]
        if T >= 2048:
            ce = chunked_xent(h, lambda hc: self._logits(params, hc), labels, mask)
        else:
            ce = softmax_xent(self._logits(params, h), labels, mask)
        total = ce
        metrics = {"ce": ce}
        if cfg.moe is not None:
            total = total + cfg.moe.aux_loss_weight * aux
            metrics["aux"] = aux
        if cfg.mtp_depth:
            mtp_ce = self._mtp_loss(params, h, batch)
            total = total + 0.3 * mtp_ce
            metrics["mtp_ce"] = mtp_ce
        metrics["loss"] = total
        return total, metrics

    def _mtp_loss(self, params, h, batch) -> jnp.ndarray:
        """DeepSeek-V3 multi-token prediction: one extra block predicts t+2."""
        cfg = self.cfg
        labels = batch["labels"]
        emb_next = embed(params["embed"], labels)      # embedding of token t+1
        z = jnp.concatenate([h.astype(emb_next.dtype), emb_next], axis=-1)
        z = z @ params["mtp"]["proj"]
        z, _, _ = _block_apply(cfg, "attn_dense" if cfg.moe else "attn",
                               params["mtp"]["block"], z, "train")
        z = rmsnorm(params["mtp"]["norm"], z)
        labels2 = jnp.roll(labels, -1, axis=1)
        mask = jnp.ones_like(labels2, jnp.float32).at[:, -1].set(0.0)
        if labels2.shape[1] >= 2048:
            return chunked_xent(z, lambda hc: self._logits(params, hc), labels2, mask)
        return softmax_xent(self._logits(params, z), labels2, mask)

    # ------------------------------------------------------------- serve ---
    def cache(self, batch: int, max_len: int, as_spec: bool = False) -> Dict:
        cfg, plan = self.cfg, self.plan
        mk = lambda kind: _block_cache(cfg, kind, batch, max_len, self.dtype, as_spec)
        lead = [mk(k) for k in plan.lead]
        tail = [mk(k) for k in plan.tail]
        blocks = None
        if plan.n_scan:
            sb = {f"b{i}": mk(k) for i, k in enumerate(plan.pattern)}
            if as_spec:
                blocks = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct((plan.n_scan, *s.shape), s.dtype),
                    sb,
                    is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
                )
            else:
                blocks = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (plan.n_scan, *a.shape)).copy(), sb
                )
        return {"lead": lead, "blocks": blocks, "tail": tail}

    def prefill(self, params, batch, max_len: int):
        """Process the prompt; returns (last-token logits, caches)."""
        x = self._embed_inputs(params, batch)
        caches = self.cache(x.shape[0], max_len)
        x, new_caches, _ = self._stack(params, x, "prefill", caches, max_len)
        h = rmsnorm(params["final_norm"], x[:, -1:])
        return self._logits(params, h), new_caches

    def decode_step(self, params, caches, tokens):
        """One token for every sequence. tokens: [B, 1] → logits [B, 1, V]."""
        x = embed(params["embed"], tokens)
        x, new_caches, _ = self._stack(params, x, "decode", caches)
        h = rmsnorm(params["final_norm"], x)
        return self._logits(params, h), new_caches
