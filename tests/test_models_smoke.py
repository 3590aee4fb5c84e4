"""Per-architecture smoke tests: one forward/train step on CPU, shape and
finiteness checks, decode-vs-forward consistency (deliverable f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, ShapeConfig, get_config
from repro.models import Model, input_specs

SHAPE = ShapeConfig("smoke", seq_len=32, global_batch=2, kind="train")


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_shapes_and_finite(arch):
    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = input_specs(cfg, SHAPE, concrete=True, dtype=jnp.float32)
    loss, metrics = model.loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: loss not finite"
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert bool(jnp.all(jnp.isfinite(g))), f"{arch}: non-finite grad at {path}"


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_hidden_shape(arch):
    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = input_specs(cfg, SHAPE, concrete=True, dtype=jnp.float32)
    h, aux = model.forward(params, batch)
    T = SHAPE.seq_len
    assert h.shape == (SHAPE.global_batch, T, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(h.astype(jnp.float32))))


@pytest.mark.parametrize(
    "arch", [a for a in ARCHS if get_config(a).causal]
)
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch, smoke=True).with_overrides(dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    T, B, max_len = 24, 2, 32
    batch = input_specs(cfg, ShapeConfig("p", T, B, "prefill"), concrete=True,
                        dtype=jnp.float32)
    logits_p, cache = model.prefill(params, batch, max_len)

    h0, _ = model.forward(params, batch)
    ref_p = model._logits(params, h0)[:, -1:]
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(ref_p),
                               atol=2e-4, rtol=1e-3)

    tok = jax.random.randint(jax.random.PRNGKey(9), (B, 1), 0, cfg.vocab_size)
    logits_d, cache = model.decode_step(params, cache, tok)
    batch2 = dict(batch)
    batch2["tokens"] = jnp.concatenate([batch["tokens"], tok], axis=1)
    h, _ = model.forward(params, batch2)
    ref_d = model._logits(params, h)[:, -1:]
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(ref_d),
                               atol=5e-3, rtol=1e-2)


def test_encoder_has_no_decode_shapes():
    from repro.configs import shape_cells

    cells = dict((s.name, skip) for s, skip in shape_cells("hubert-xlarge"))
    assert cells["decode_32k"] is not None
    assert cells["long_500k"] is not None
    assert cells["train_4k"] is None


def test_long_context_only_for_subquadratic():
    from repro.configs import shape_cells

    for arch in ARCHS:
        cells = dict((s.name, skip) for s, skip in shape_cells(arch))
        family = get_config(arch).family
        if family in ("hybrid", "ssm"):
            assert cells["long_500k"] is None, arch
        else:
            assert cells["long_500k"] is not None, arch


def test_param_counts_scale_with_config():
    """Full configs must be far larger than smoke ones (sanity on specs)."""
    from repro.models import param_count

    for arch in ARCHS:
        full = param_count(Model(get_config(arch)).specs())
        smoke = param_count(Model(get_config(arch, smoke=True)).specs())
        assert full > 50 * smoke, arch


@pytest.mark.parametrize(
    "arch,expected_b",
    [("llama3-8b", 8.0e9), ("llama3.2-1b", 1.2e9), ("deepseek-v2-236b", 236e9),
     ("deepseek-v3-671b", 671e9), ("deepseek-v2-lite", 15.7e9)],
)
def test_param_counts_match_published(arch, expected_b):
    from repro.models import param_count

    n = param_count(Model(get_config(arch)).specs())
    assert 0.75 * expected_b < n < 1.30 * expected_b, (
        f"{arch}: {n / 1e9:.1f}B vs published {expected_b / 1e9:.0f}B"
    )
