"""Benchmark 5 — Pallas kernels (interpret mode): correctness deltas + block
shape sweep. Wall times on CPU interpret mode are NOT TPU estimates; the
derived column carries the VMEM working-set math that sizes the tiles.
"""

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def run(report):
    B, T, H, K, d = 1, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, K, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, K, d), jnp.float32)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    for qb, kb in ((64, 64), (128, 128), (64, 256)):
        t0 = time.perf_counter()
        out = ops.flash_attention(q, k, v, True, 0, qb, kb, None)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        err = float(jnp.max(jnp.abs(out - expect)))
        vmem_kb = (qb * d + kb * d + qb * kb + qb * d) * 4 / 1024
        report(
            f"kernel_bench/flash_qb{qb}_kb{kb}", dt * 1e6,
            f"err={err:.1e} vmem_working_set={vmem_kb:.0f}KiB "
            f"(v5e VMEM 16MiB)",
        )

