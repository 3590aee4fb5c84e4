"""Step builders shared by the trainer, the server and the dry-run.

The pod axis — the slow fabric, the paper's "remote class" — is expressed
with a *leading pod dimension* (vmap-over-pod) rather than a manual shard_map
around the whole model: XLA's SPMD partitioner mis-handles gathers inside
manual subgroups, and the vmap formulation lowers to exactly the cohort
schedule anyway:

* per-pod gradients come out of ``vmap`` with a leading ``[P, ...]`` dim
  sharded over ``pod``;
* within each pod, GSPMD reduce-scatters gradients across ``data`` (FSDP) —
  the *cohort election*: each chip ends up leader of a 1/|data| fragment;
* the cross-pod exchange is the dim-0 mean — one collective over ``pod``
  carrying only fragments (the elected leaders' 2-party protocol), optionally
  int8+error-feedback via a collectives-only shard_map;
* the FSDP all-gather redistributes — the cohort hand-off.

Modes (``RunConfig.sync_mode``):
  flat  — paper-baseline: batch sharded over (pod×data) jointly; XLA emits one
          logical all-reduce spanning the DCN.
  sync  — cohort schedule above; numerically identical to flat.
  local — budgeted: per-pod parameters + optimizer (leading pod dim in the
          train state); pods reconcile by parameter averaging every
          ``sync_budget`` steps (bounded staleness, straggler mitigation —
          the paper's fairness budget).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, RunConfig, ShapeConfig
from ..models import Model
from ..models.layers import activation_rules
from ..optim import adamw_update, adamw_init, cosine_schedule
from ..optim.adamw import AdamWState
from ..models import input_specs
from ..sharding import ACT_RULES, batch_pspec, cache_pspecs, param_pspecs
from ..sharding.rules import fitted_shardings


# ---------------------------------------------------------------------------
# int8 + error-feedback cross-pod exchange (collectives-only shard_map: safe)
# ---------------------------------------------------------------------------
def _int8_pod_mean(grads_p, ef_p, mesh: Mesh):
    """Mean over the leading pod dim with int8 wire format + error feedback.

    grads_p/ef_p leaves: [P, ...] sharded P('pod', ...). Returns
    (mean [...] replicated over pod, new_ef [P, ...]).
    """
    from ..core.cohort import _ef_quantize

    def body(gp, ep):
        # local block: leading dim 1 (this pod's slice)
        g, e = gp[0], ep[0]
        q, scale, new_e = _ef_quantize(g, e)
        qs = jax.lax.all_gather(q, "pod", axis=0)          # int8 on the wire
        ss = jax.lax.all_gather(scale, "pod", axis=0)
        npods = qs.shape[0]
        deq = qs.astype(g.dtype) * ss.reshape((npods,) + (1,) * g.ndim).astype(g.dtype)
        return jnp.sum(deq, axis=0) / npods, new_e[None]

    def exchange(gs, es):
        flat_g, tdef = jax.tree.flatten(gs)
        flat_e, _ = jax.tree.flatten(es)
        outs = [body(g, e) for g, e in zip(flat_g, flat_e)]
        return (
            jax.tree.unflatten(tdef, [o[0] for o in outs]),
            jax.tree.unflatten(tdef, [o[1] for o in outs]),
        )

    # Fully manual: the body is collectives-only (pod all-gather + elementwise
    # quantize), and inputs are replicated over data/model, so claiming every
    # axis is equivalent — and partial-manual islands trip XLA partitioner
    # bugs on older jax (same reason as the MoE island, see models/moe.py).
    fn = jax.shard_map(
        exchange,
        mesh=mesh,
        in_specs=(P("pod"), P("pod")),
        out_specs=(P(), P("pod")),
        axis_names=frozenset(mesh.axis_names),
        check_vma=False,
    )
    return fn(grads_p, ef_p)


def _pod_split(batch, npods: int):
    """[B, ...] → [P, B/P, ...] with dim0 on ``pod`` and dim1 on ``data``."""
    def one(a):
        a = a.reshape(npods, a.shape[0] // npods, *a.shape[1:])
        return jax.lax.with_sharding_constraint(
            a, P("pod", "data", *([None] * (a.ndim - 2)))
        )
    return jax.tree.map(one, batch)


# ---------------------------------------------------------------------------
# Train state
# ---------------------------------------------------------------------------
def train_state_specs(model: Model, run: RunConfig, npods: int = 1):
    """(ShapeDtypeStruct tree, PartitionSpec tree) for the full train state.

    ``local`` mode keeps per-pod parameters/optimizer: every leaf gets a
    leading pod dim sharded over ``pod``.
    """
    pspecs = param_pspecs(model.specs())
    pshapes = model.param_shapes()
    sdtype = jnp.float32 if run.optimizer_state_dtype == "float32" else jnp.bfloat16
    opt_shapes = {
        "step": jax.ShapeDtypeStruct((), jnp.int32),
        "mu": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, sdtype), pshapes),
        "nu": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, sdtype), pshapes),
    }
    opt_specs = {"step": P(), "mu": pspecs, "nu": pspecs}
    shapes = {"params": pshapes, "opt": opt_shapes}
    specs = {"params": pspecs, "opt": opt_specs}
    if run.sync_mode == "local" and npods > 1:
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((npods, *s.shape), s.dtype),
            shapes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        specs = jax.tree.map(
            lambda ps: P("pod", *ps), specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        shapes["opt"]["step"] = jax.ShapeDtypeStruct((npods,), jnp.int32)
    if run.compress_int8 and npods > 1 and run.sync_mode == "sync":
        shapes["ef"] = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((npods, *s.shape), jnp.float32),
            pshapes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        specs["ef"] = jax.tree.map(
            lambda ps: P("pod", *ps), pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
    return shapes, specs


def init_train_state(model: Model, run: RunConfig, rng, npods: int = 1) -> Dict:
    params = model.init(rng)
    sdtype = jnp.float32 if run.optimizer_state_dtype == "float32" else jnp.bfloat16
    opt = adamw_init(params, sdtype)
    state = {"params": params, "opt": {"step": opt.step, "mu": opt.mu, "nu": opt.nu}}
    if run.sync_mode == "local" and npods > 1:
        state = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (npods, *a.shape)).copy(), state
        )
    if run.compress_int8 and npods > 1 and run.sync_mode == "sync":
        state["ef"] = jax.tree.map(
            lambda p: jnp.zeros((npods, *p.shape), jnp.float32), params
        )
    return state


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
def _grad_fn(loss_fn, microbatches: int, batch_axes=("data",)):
    """value_and_grad with optional gradient accumulation over microbatches.

    Accumulation bounds live activation memory to one microbatch's worth —
    the memory-roofline knob (grads accumulate in fp32, sharded like params).
    ``batch_axes`` keeps the row sharding (incl. ``pod`` in flat multi-pod
    mode) across the microbatch reshape.
    """
    vg = jax.value_and_grad(loss_fn, has_aux=True)
    if microbatches <= 1:
        return vg

    def accumulated(params, batch):
        def split(a):
            a = a.reshape(microbatches, a.shape[0] // microbatches, *a.shape[1:])
            return jax.lax.with_sharding_constraint(
                a, P(None, batch_axes, *([None] * (a.ndim - 2)))
            )

        bm = jax.tree.map(split, batch)

        def mb(carry, mbatch):
            gacc, lacc, macc = carry
            (l, m), g = vg(params, mbatch)
            gacc = jax.tree.map(
                lambda ga, gi: ga + gi.astype(jnp.float32), gacc, g
            )
            macc = jax.tree.map(lambda a, b: a + b, macc, m)
            return (gacc, lacc + l, macc), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        # First microbatch outside the scan initialises the accumulators.
        (l_first, m_first), g_first = vg(
            params, jax.tree.map(lambda a: a[0], bm)
        )
        gacc = jax.tree.map(lambda ga, gi: ga + gi.astype(jnp.float32), g0, g_first)
        rest = jax.tree.map(lambda a: a[1:], bm)
        (gacc, lsum, msum), _ = jax.lax.scan(
            mb, (gacc, l_first, m_first), rest
        )
        n = float(microbatches)
        grads = jax.tree.map(lambda g: (g / n), gacc)
        return (lsum / n, jax.tree.map(lambda m: m / n, msum)), grads

    return accumulated


def _adamw_piece(run: RunConfig, params, grads, opt_dict):
    opt = AdamWState(opt_dict["step"], opt_dict["mu"], opt_dict["nu"])
    lr = cosine_schedule(
        opt.step, peak_lr=run.learning_rate, warmup=run.warmup_steps,
        total=run.total_steps,
    )
    params, opt, om = adamw_update(
        params, grads, opt, lr,
        weight_decay=run.weight_decay, grad_clip=run.grad_clip,
    )
    return params, {"step": opt.step, "mu": opt.mu, "nu": opt.nu}, om


def _act_rules(multi_pod: bool, pod_in_batch: bool):
    """Activation rules; the batch dim carries (pod, data) whenever the pod
    axis is NOT peeled off by vmap (flat-mode train, all serving) — else the
    first with_sharding_constraint silently replicates work across pods."""
    rules = dict(ACT_RULES)
    if multi_pod and pod_in_batch:
        rules["batch"] = ("pod", "data")
    return rules


def build_train_step(
    model: Model,
    run: RunConfig,
    mesh: Mesh,
    shape: ShapeConfig,
) -> Tuple[Callable, Any, Any, Any]:
    """Returns (jitted step, state shapes, state shardings, batch shardings)."""
    cfg = model.cfg
    multi_pod = "pod" in mesh.shape
    npods = mesh.shape.get("pod", 1)
    state_shapes, state_pspecs = train_state_specs(model, run, npods)
    mode = run.sync_mode if multi_pod else "flat"

    def loss_fn(p, b):
        return model.loss(p, b)

    # In sync/local modes the pod dim is peeled off by vmap before grad_fn
    # sees the batch; in flat multi-pod mode rows stay (pod×data)-sharded.
    _gf_axes = (
        ("pod", "data")
        if (multi_pod and run.sync_mode in ("flat", "none"))
        else ("data",)
    )
    grad_fn = _grad_fn(loss_fn, run.microbatches, _gf_axes)
    rules = _act_rules(multi_pod, run.sync_mode in ("flat", "none"))

    def step(state, batch):
        with activation_rules(rules):
            if mode in ("flat", "none") or not multi_pod:
                (loss, metrics), grads = grad_fn(state["params"], batch)
                params, opt, om = _adamw_piece(run, state["params"], grads,
                                               state["opt"])
                new_state = {"params": params, "opt": opt}
                if "ef" in state:
                    new_state["ef"] = state["ef"]
            elif mode == "sync":
                bp = _pod_split(batch, npods)
                (loss_p, metrics_p), grads_p = jax.vmap(
                    grad_fn, in_axes=(None, 0),
                )(state["params"], bp)
                loss = jnp.mean(loss_p)
                metrics = jax.tree.map(jnp.mean, metrics_p)
                new_state = {}
                if run.compress_int8:
                    grads, new_ef = _int8_pod_mean(grads_p, state["ef"], mesh)
                    new_state["ef"] = new_ef
                else:
                    # The cohort exchange: fragment mean over the pod dim.
                    grads = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads_p)
                params, opt, om = _adamw_piece(run, state["params"], grads,
                                               state["opt"])
                new_state.update({"params": params, "opt": opt})
            elif mode == "local":
                bp = _pod_split(batch, npods)
                (loss_p, metrics_p), grads_p = jax.vmap(
                    grad_fn, in_axes=(0, 0),
                )(state["params"], bp)
                loss = jnp.mean(loss_p)
                metrics = jax.tree.map(jnp.mean, metrics_p)
                params_p, opt_p, om = jax.vmap(
                    functools.partial(_adamw_piece, run)
                )(state["params"], grads_p, state["opt"])
                om = jax.tree.map(jnp.mean, om)
                # Budgeted reconcile: pods average every `sync_budget` steps.
                do_sync = (opt_p["step"][0] % run.sync_budget) == 0
                params_p = jax.lax.cond(
                    do_sync,
                    lambda ps: jax.tree.map(
                        lambda a: jnp.broadcast_to(
                            jnp.mean(a, axis=0, keepdims=True), a.shape
                        ),
                        ps,
                    ),
                    lambda ps: ps,
                    params_p,
                )
                new_state = {"params": params_p, "opt": opt_p}
            else:
                raise ValueError(mode)
            metrics = dict(metrics)
            metrics.update(om)
            metrics["loss"] = loss
            return new_state, metrics

    batch_axes = ("pod", "data") if multi_pod else ("data",)
    bspecs = batch_pspec(cfg, shape, batch_axes=batch_axes)
    bshapes = input_specs(cfg, shape)
    state_sh = fitted_shardings(state_shapes, state_pspecs, mesh)
    batch_sh = fitted_shardings(bshapes, bspecs, mesh)
    jitted = jax.jit(
        step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )
    return jitted, state_shapes, state_sh, batch_sh


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------
def build_encode_step(model: Model, mesh: Mesh, shape: ShapeConfig):
    """Encoder-only forward → logits (hubert 'prefill')."""
    cfg = model.cfg
    multi_pod = "pod" in mesh.shape
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    pspecs = param_pspecs(model.specs())
    bspecs = batch_pspec(cfg, shape, batch_axes=batch_axes)

    rules = _act_rules(multi_pod, True)

    def encode(params, batch):
        with activation_rules(rules):
            h, _ = model.forward(params, batch)
            return model._logits(params, h)

    param_sh = fitted_shardings(model.param_shapes(), pspecs, mesh)
    batch_sh = fitted_shardings(input_specs(cfg, shape), bspecs, mesh)
    return jax.jit(encode, in_shardings=(param_sh, batch_sh))


def build_prefill_step(model: Model, mesh: Mesh, shape: ShapeConfig, max_len: int):
    cfg = model.cfg
    multi_pod = "pod" in mesh.shape
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    pspecs = param_pspecs(model.specs())
    bspecs = batch_pspec(cfg, shape, batch_axes=batch_axes)
    cache_spec = model.cache(shape.global_batch, max_len, as_spec=True)
    cspecs = cache_pspecs(cache_spec, batch_axes=batch_axes, mesh=mesh)

    rules = _act_rules(multi_pod, True)

    def prefill(params, batch):
        with activation_rules(rules):
            return model.prefill(params, batch, max_len)

    param_sh = fitted_shardings(model.param_shapes(), pspecs, mesh)
    batch_sh = fitted_shardings(input_specs(cfg, shape), bspecs, mesh)
    cache_sh = fitted_shardings(cache_spec, cspecs, mesh)
    jitted = jax.jit(
        prefill,
        in_shardings=(param_sh, batch_sh),
        out_shardings=(None, cache_sh),
    )
    return jitted, cache_spec, (param_sh, batch_sh, cache_sh)


def build_decode_step(model: Model, mesh: Mesh, shape: ShapeConfig, max_len: int):
    """serve_step: one new token for every sequence against a seq_len cache."""
    cfg = model.cfg
    multi_pod = "pod" in mesh.shape
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    pspecs = param_pspecs(model.specs())
    cache_spec = model.cache(shape.global_batch, max_len, as_spec=True)
    cspecs = cache_pspecs(cache_spec, batch_axes=batch_axes, mesh=mesh)

    rules = _act_rules(multi_pod, True)

    def decode(params, caches, tokens):
        with activation_rules(rules):
            return model.decode_step(params, caches, tokens)

    param_sh = fitted_shardings(model.param_shapes(), pspecs, mesh)
    cache_sh = fitted_shardings(cache_spec, cspecs, mesh)
    tok_shape = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tok_sh = fitted_shardings(tok_shape, P(batch_axes), mesh)
    jitted = jax.jit(
        decode,
        in_shardings=(param_sh, cache_sh, tok_sh),
        out_shardings=(None, cache_sh),
        donate_argnums=(1,),
    )
    return jitted, cache_spec, (param_sh, cache_sh, tok_sh)
