"""Per-architecture smoke tests: one forward/train step on CPU, shape and
finiteness checks, decode-vs-forward consistency (deliverable f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import ARCHS, ShapeConfig, get_config
from repro.models import Model, attention as attn, input_specs

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


CAUSAL = [a for a in ARCHS if get_config(a).causal]


@pytest.mark.parametrize(
    "arch,T,steps",
    [pytest.param(a, 24, 1, id=a) for a in CAUSAL]
    + [pytest.param(a, 14, 4, id=f"{a}-4steps") for a in CAUSAL],
)
def test_prefill_decode_matches_forward(arch, T, steps):
    """Prefill's last logits, then each decode step's, equal ``forward``
    over the prompt so far, and afterwards every KV or latent cache holds
    what a prefill over the same tokens writes.  Recurrentgemma's 16-slot
    ring buffer has wrapped in a 24-token prefill, and wraps during decode
    after a 14-token one."""
    cfg = get_config(arch, smoke=True).with_overrides(dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, max_len = 2, 32
    batch = input_specs(cfg, ShapeConfig("p", T, B, "prefill"), concrete=True,
                        dtype=jnp.float32)
    logits_p, cache = model.prefill(params, batch, max_len)

    h0, _ = model.forward(params, batch)
    ref_p = model._logits(params, h0)[:, -1:]
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(ref_p),
                               atol=2e-4, rtol=1e-3)

    decode = jax.jit(model.decode_step)
    toks = jax.random.randint(jax.random.PRNGKey(9), (B, steps), 0,
                              cfg.vocab_size)
    grown = dict(batch)
    for i in range(steps):
        logits_d, cache = decode(params, cache, toks[:, i:i + 1])
        grown["tokens"] = jnp.concatenate([batch["tokens"], toks[:, :i + 1]],
                                          axis=1)
        h, _ = model.forward(params, grown)
        ref_d = model._logits(params, h)[:, -1:]
        np.testing.assert_allclose(np.asarray(logits_d), np.asarray(ref_d),
                                   atol=5e-3, rtol=1e-2, err_msg=f"step {i}")

    _, want = model.prefill(params, grown, max_len)
    layers = lambda c: [*c["lead"], *(c["blocks"] or {}).values(), *c["tail"]]
    pairs = [(c, w) for c, w in zip(layers(cache), layers(want))
             if isinstance(c, (attn.KVCache, attn.MLACache))]
    assert bool(pairs) == ("attn" in cfg.block_pattern)
    for got, ref in pairs:
        assert np.all(np.asarray(got.length) == T + steps)
        for g, r in zip(got[:-1], ref[:-1]):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       atol=1e-4, rtol=1e-4)


def test_decode_cache_write_record():
    """``decode.cache_write`` counts the layers whose cache takes one row a
    step and those whose state a step replaces: DeepSeek-V2-Lite's chip
    share (27 latent caches: the dense first layer and 26 scanned, 576
    bf16 values a row, batch 32) at trace time, with no arrays made."""
    def record(cfg, batch, max_len):
        model = Model(cfg)
        obs.reset()
        jax.eval_shape(model.decode_step, model.param_shapes(),
                       model.cache(batch, max_len, as_spec=True),
                       jax.ShapeDtypeStruct((batch, 1), jnp.int32))
        (rec,) = obs.spans("decode.cache_write")
        return rec.attrs

    share = get_config("deepseek-v2-lite").with_overrides(moe={"num_held": 16})
    assert record(share, 32, 1280) == {
        "row_layers": 27, "state_layers": 0,
        "row_bytes_per_step": 27 * 32 * (512 + 64) * 2}
    # recurrentgemma's smoke stack: rec, rec, attn scanned once, then two
    # rec tail layers; a row is K and V of one 16-wide KV head in bf16.
    assert record(get_config("recurrentgemma-9b", smoke=True), 2, 32) == {
        "row_layers": 1, "state_layers": 4,
        "row_bytes_per_step": 2 * 2 * 16 * 2}
    xl = record(get_config("xlstm-1.3b", smoke=True), 2, 32)
    assert xl["row_layers"] == 0 and xl["row_bytes_per_step"] == 0
    assert xl["state_layers"] == 4


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
