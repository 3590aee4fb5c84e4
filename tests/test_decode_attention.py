"""Decode attention per KV head: equal to the quadratic reference, and on a
mesh that shards the cache it reads the cache where it lies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import decode_attention, full_attention_reference


@pytest.mark.parametrize("window", [0, 5], ids=["full", "window5"])
@pytest.mark.parametrize("H,K", [(32, 2), (32, 8), (8, 8), (4, 1)])
def test_decode_attention_equals_reference(H, K, window):
    """Each row's query against its first ``length - 1`` slots (the last
    ``window - 1`` of them if windowed) and its own new key and value,
    held apart from the cache, equals the reference's last query row over
    its first ``length`` keys, in bf16."""
    B, S, d = 3, 24, 32
    ks = jax.random.split(jax.random.PRNGKey(H * 100 + K * 10 + window), 3)
    q = jax.random.normal(ks[0], (B, S, H, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, K, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, K, d), jnp.bfloat16)
    length = np.array([S, 11, 1], np.int32)
    last = lambda x: jnp.stack([x[b, n - 1] for b, n in enumerate(length)])[:, None]
    slot = np.arange(S)[None, :]
    valid = slot < length[:, None] - 1
    if window:
        valid &= slot >= length[:, None] - window
    got = decode_attention(last(q), k, v, jnp.asarray(valid), last(k), last(v))
    assert got.shape == (B, 1, H, d) and got.dtype == jnp.bfloat16
    for b, n in enumerate(length):
        want = full_attention_reference(
            q[b:b + 1, :n], k[b:b + 1, :n], v[b:b + 1, :n], window=window)[:, -1]
        np.testing.assert_allclose(np.asarray(got[b], np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-2, rtol=2e-2)


def test_decode_attention_sharded_reads_cache_in_place(multidevice):
    """``heads`` split 4 ways over K = 2 (the cache sharded on its head
    dim) and over K = 4 (sharded on K): the output equals one chip's, and no
    collective carries an array as large as a chip's share of the cache
    (widening the cache to H heads all-gathers it)."""
    out = multidevice(
        """
import math, re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.models.attention import decode_attention, kv_model_dim
from repro.models.layers import activation_rules
from repro.sharding import ACT_RULES

B, S, H, d = 2, 64, 8, 64
valid = jnp.arange(S)[None, :] < jnp.array([[S - 1], [8]])
COLL = re.compile(r"= (.*?) (all-gather|all-to-all|all-reduce|"
                  r"collective-permute|reduce-scatter)(-start)?\\(")

for K in (2, 4):
    ks = jax.random.split(jax.random.PRNGKey(K), 3)
    q = jax.random.normal(ks[0], (B, 1, H, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, K, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, K, d), jnp.bfloat16)
    k_new, v_new = k[:, -1:], v[:, -1:]
    want = np.asarray(decode_attention(q, k, v, valid, k_new, v_new),
                      np.float32)
    mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
    cache = [None] * 4
    cache[kv_model_dim(K, d, 4)] = "model"
    shard = lambda *spec: NamedSharding(mesh, P(*spec))
    with jax.set_mesh(mesh), activation_rules(ACT_RULES):
        step = jax.jit(decode_attention, in_shardings=(
            shard(None, None, "model", None), shard(*cache), shard(*cache),
            shard(), shard(*cache), shard(*cache)))
        got = np.asarray(step(q, k, v, valid, k_new, v_new), np.float32)
        text = step.lower(q, k, v, valid, k_new, v_new).compile().as_text()
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    share = B * S * K * d // 4
    moved = [math.prod(int(n) for n in dims.split(",") if n)
             for lhs, _, _ in COLL.findall(text)
             for dims in re.findall(r"\\w+\\[([\\d,]*)\\]", lhs)]
    assert max(moved, default=0) < share, (K, moved, share)
print("OK")
""",
        devices=4,
    )
    assert "OK" in out


def test_decode_steps_on_a_mesh_equal_one_device(multidevice):
    """Prefill and three decode steps through the serving builders on a
    (1, 4) mesh, where ``model`` shards the GQA cache on its head dim
    (K = 2) and the latent cache on its slots (1024 of them), equal the
    same steps on one device: the rows written after the layer scan land
    in the sharded cache where they belong."""
    out = multidevice(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ShapeConfig, get_config
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_decode_step, build_prefill_step
from repro.models import Model, input_specs

B, T, S = 4, 12, 1024
cfgs = {"gqa": get_config("llama3.2-1b", smoke=True),
        "mla": get_config("deepseek-v2-lite", smoke=True).with_overrides(
            moe=None, num_layers=3)}
for name, cfg in cfgs.items():
    cfg = cfg.with_overrides(dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = input_specs(cfg, ShapeConfig("p", T, B, "prefill"), concrete=True,
                        dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, 3), 0, cfg.vocab_size)
    runs = []
    for shape in ((1, 1), (1, 4)):
        mesh = make_mesh(shape, ("data", "model"),
                         devices=jax.devices()[:shape[0] * shape[1]])
        with jax.set_mesh(mesh):
            prefill, _, (param_sh, batch_sh, cache_sh) = build_prefill_step(
                model, mesh, ShapeConfig("p", T, B, "prefill"), S)
            decode, _, _ = build_decode_step(
                model, mesh, ShapeConfig("d", S, B, "decode"), S)
            p = jax.device_put(params, param_sh)
            _, cache = prefill(p, jax.device_put(batch, batch_sh))
            logits = []
            for i in range(3):
                out, cache = decode(p, cache, toks[:, i:i + 1])
                logits.append(np.asarray(out))
            assert jax.tree.map(lambda a: a.sharding, cache) == cache_sh
            runs.append((logits, jax.tree.map(np.asarray, cache)))
    (l1, c1), (l4, c4) = runs
    np.testing.assert_allclose(np.stack(l4), np.stack(l1), atol=1e-4, rtol=1e-4)
    for a, b in zip(jax.tree.leaves(c4), jax.tree.leaves(c1)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
print("OK")
""",
        devices=4,
    )
    assert "OK" in out
