"""The expert-share layer: one chip's share of the routed experts, with a
router over all of them, no capacity, and both ways of running the held
experts (grouped products, and the dense product small batches take).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/test_moe_share.py
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import ShapeConfig, get_config
from repro.models import Model, input_specs, moe
from repro.models.layers import mlp
from repro.models.moe import moe_ffn, moe_spec
from repro.models.specs import init_params

ROOT = Path(__file__).resolve().parents[1]
SMOKE = get_config("deepseek-v2-lite", smoke=True).with_overrides(dtype="float32")


@pytest.fixture(params=["grouped", "dense"])
def path(request, monkeypatch):
    """Every token count through the grouped products, or through the
    dense product over the held experts that small batches take."""
    monkeypatch.setattr(moe, "DENSE_TOKENS",
                        0 if request.param == "grouped" else 1 << 30)
    return request.param


def _layer(cfg, seed=0):
    params = init_params(moe_spec(cfg, jnp.float32), jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 24, cfg.d_model))
    return params, x


def _share(cfg, params, first, n):
    """A chip's config and weights: experts [first, first + n), the router
    and the shared experts whole."""
    sub = dict(params, wi=params["wi"][first:first + n],
               wo=params["wo"][first:first + n])
    return cfg.with_overrides(moe={"first_held": first, "num_held": n}), sub


def test_four_shares_sum_to_the_uncut_layer(path):
    """At SMOKE size (8 experts, top-3), four chips of 2 experts each: the
    sum of their outputs, with the shared experts counted once, is the
    layer that holds all 8."""
    params, x = _layer(SMOKE)
    whole, _ = moe_ffn(params, x, SMOKE)
    shared = mlp(params["shared"], x, "swiglu")
    parts = [moe_ffn(p, x, c)[0] - shared
             for c, p in (_share(SMOKE, params, 2 * i, 2) for i in range(4))]
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=1e-5, rtol=1e-5)
    # And the uncut layer is the one-hot oracle's whenever no slot runs out.
    roomy = SMOKE.with_overrides(moe={"capacity_factor": 8.0})
    oracle, _ = moe_ffn(params, x, roomy, dispatch="onehot")
    np.testing.assert_allclose(np.asarray(whole), np.asarray(oracle), atol=1e-5)


def test_every_token_on_one_expert_drops_nothing(path):
    """The router sends every token's first choice to expert 5: its group
    holds all 48 rows, far past the 1.25 capacity a group would have, and
    the grouped path still equals the one-hot oracle given room for all of
    them, on the full layer and on the share that holds expert 5."""
    params, x = _layer(SMOKE, seed=3)
    x = x.at[..., 0].set(6.0)
    params = dict(params, router=params["router"].at[0, 5].set(10.0))
    roomy = SMOKE.with_overrides(moe={"capacity_factor": 16.0})
    oracle, _ = moe_ffn(params, x, roomy, dispatch="onehot")
    tight, _ = moe_ffn(params, x, SMOKE, dispatch="onehot")
    got, _ = moe_ffn(params, x, SMOKE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(oracle), atol=1e-5)
    assert float(jnp.max(jnp.abs(tight - oracle))) > 1e-2  # capacity drops
    cfg, sub = _share(SMOKE, params, 4, 2)
    shared = mlp(params["shared"], x, "swiglu")
    others = [moe_ffn(p, x, c)[0] - shared
              for c, p in (_share(SMOKE, params, f, 2) for f in (0, 2, 6))]
    mine, _ = moe_ffn(sub, x, cfg)
    np.testing.assert_allclose(np.asarray(mine + sum(others)),
                               np.asarray(oracle), atol=1e-5)


def test_prefill_then_decode_equals_forward_on_a_share(path):
    """A chip holding experts 2-5 of 8: prefill then two decode steps give
    the full forward's logits, with no capacity to raise."""
    cfg = SMOKE.with_overrides(moe={"first_held": 2, "num_held": 4})
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    T, B = 24, 3
    batch = input_specs(cfg, ShapeConfig("p", T, B, "prefill"), concrete=True,
                        dtype=jnp.float32)
    logits, cache = model.prefill(params, batch, T + 2)
    toks = batch["tokens"]
    for step in range(2):
        h, _ = model.forward(params, {"tokens": toks})
        ref = model._logits(params, h)[:, -1:]
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   atol=2e-4, rtol=1e-3)
        tok = jax.random.randint(jax.random.PRNGKey(step), (B, 1), 0,
                                 cfg.vocab_size)
        logits, cache = model.decode_step(params, cache, tok)
        toks = jnp.concatenate([toks, tok], axis=1)


def test_program_config_of_the_committed_cell():
    sys.path.insert(0, str(ROOT))
    from bench.serve import program_config

    raw = json.loads((ROOT / "bench/configs/deepseek-v2-lite-ep4.json").read_text())
    cfg = program_config(raw)
    m = cfg.moe
    assert (m.num_experts, m.first_held, m.num_held, m.held) == (64, 0, 16, 16)
    assert (m.top_k, m.d_expert, m.num_shared, m.router) == (6, 1408, 2, "softmax")
    assert raw["n_routed_experts"] == m.num_held
    assert raw["published"]["n_routed_experts"] == m.num_experts
    assert cfg.yarn.factor == 40 and cfg.mla.q_lora_rank == 0
    shapes = Model(cfg).param_shapes()["blocks"]["b0"]["ffn"]
    assert shapes["router"].shape == (26, 2048, 64)
    assert shapes["wi"].shape == (26, 16, 2048, 2816)
    # The registry entry it starts from is unchanged.
    assert get_config("deepseek-v2-lite").moe == dataclasses.replace(
        m, num_held=0)


def test_moe_plan_record():
    obs.reset()
    cfg, sub = _share(SMOKE, _layer(SMOKE)[0], 0, 4)
    jax.jit(lambda p, x: moe_ffn(p, x, cfg)[0]).lower(
        sub, jnp.zeros((1, 4, cfg.d_model)))
    plans = obs.spans("moe.plan")
    assert len(plans) == 1
    assert plans[0].attrs == {"experts_held": 4, "experts_routed": 8,
                              "top_k": 3, "dropless": True}
