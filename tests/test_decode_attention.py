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
    """Each row's query against its own first ``length`` slots equals the
    reference's last query row over those keys, in bf16."""
    B, S, d = 3, 24, 32
    ks = jax.random.split(jax.random.PRNGKey(H * 100 + K * 10 + window), 3)
    q = jax.random.normal(ks[0], (B, S, H, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, K, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, K, d), jnp.bfloat16)
    length = np.array([S, 11, 1], np.int32)
    q_last = jnp.stack([q[b, n - 1] for b, n in enumerate(length)])[:, None]
    got = decode_attention(q_last, k, v, jnp.asarray(length), window=window)
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
length = jnp.array([S, 9], jnp.int32)
COLL = re.compile(r"= (.*?) (all-gather|all-to-all|all-reduce|"
                  r"collective-permute|reduce-scatter)(-start)?\\(")

for K in (2, 4):
    ks = jax.random.split(jax.random.PRNGKey(K), 3)
    q = jax.random.normal(ks[0], (B, 1, H, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, K, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, K, d), jnp.bfloat16)
    want = np.asarray(decode_attention(q, k, v, length), np.float32)
    mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
    cache = [None] * 4
    cache[kv_model_dim(K, d, 4)] = "model"
    shard = lambda *spec: NamedSharding(mesh, P(*spec))
    with jax.set_mesh(mesh), activation_rules(ACT_RULES):
        step = jax.jit(decode_attention, in_shardings=(
            shard(None, None, "model", None), shard(*cache), shard(*cache),
            shard()))
        got = np.asarray(step(q, k, v, length), np.float32)
        text = step.lower(q, k, v, length).compile().as_text()
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
