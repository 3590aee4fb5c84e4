"""Benchmark 4 — real step wall-time on CPU for reduced configs.

Not a TPU measurement (see §Roofline for the target-hardware analysis); this
tracks relative regressions of the end-to-end step across code changes and
exercises the full train/serve paths.
"""

import time

import jax
import jax.numpy as jnp

from repro.configs import RunConfig, ShapeConfig, get_config
from repro.launch.mesh import make_mesh
from repro.launch.steps import (
    build_decode_step,
    build_prefill_step,
    build_train_step,
    init_train_state,
)
from repro.models import Model, input_specs


def _time(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out


def _time_step(step, state, batch, iters=3):
    """Train steps donate the state: thread it through the timing loop."""
    state, m = step(state, batch)
    jax.block_until_ready(m)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, m = step(state, batch)
    jax.block_until_ready(m)
    return (time.perf_counter() - t0) / iters, state


def run(report):
    mesh = make_mesh((1, 1), ("data", "model"))
    for arch in ("llama3.2-1b", "deepseek-v2-236b", "recurrentgemma-9b",
                 "xlstm-1.3b"):
        cfg = get_config(arch, smoke=True)
        model = Model(cfg)
        shape = ShapeConfig("bench", seq_len=128, global_batch=4, kind="train")
        run_cfg = RunConfig(total_steps=10)
        with jax.set_mesh(mesh):
            step, _, state_sh, batch_sh = build_train_step(
                model, run_cfg, mesh, shape
            )
            state = jax.device_put(
                init_train_state(model, run_cfg, jax.random.PRNGKey(0)), state_sh
            )
            batch = jax.device_put(
                input_specs(cfg, shape, concrete=True), batch_sh
            )
            dt, state = _time_step(step, state, batch)
        report(f"step_bench/train_{arch}", dt * 1e6,
               "smoke config, B=4 T=128, CPU")

    # decode step
    cfg = get_config("llama3.2-1b", smoke=True)
    model = Model(cfg)
    with jax.set_mesh(mesh):
        pshape = ShapeConfig("bench", seq_len=32, global_batch=4, kind="prefill")
        prefill, _, (psh, bsh, csh) = build_prefill_step(model, mesh, pshape, 64)
        dshape = ShapeConfig("bench", seq_len=64, global_batch=4, kind="decode")
        decode, _, _ = build_decode_step(model, mesh, dshape, 64)
        params = jax.device_put(model.init(jax.random.PRNGKey(0)), psh)
        batch = jax.device_put(input_specs(cfg, pshape, concrete=True), bsh)
        logits, caches = prefill(params, batch)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        logits, caches = decode(params, caches, tok)  # warmup (caches donated)
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        iters = 5
        for _ in range(iters):
            logits, caches = decode(params, caches, tok)
        jax.block_until_ready(logits)
        dt = (time.perf_counter() - t0) / iters
    report("step_bench/decode_llama3.2-1b", dt * 1e6, "per-token, CPU")
