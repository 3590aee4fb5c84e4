#!/usr/bin/env python3
"""Bring-up check: the served path on one TPU chip, end to end.

    python chip_smoke.py               # one chip: serving, decode check, kernel
    python chip_smoke.py --four-chips  # four chips: the cohort-scheduled
                                       # training mesh, sync against flat

One chip: ``launch.serve.serve`` runs full-width llama3.2-1b (random weights
from ``--seed``) twice through one lock-table ``BatchAdmission``; a decode
step is checked against a prefill over the same tokens; and the Pallas flash
kernel runs compiled by Mosaic against ``models.attention.online_attention``.
Four chips: ``launch.train.train`` takes a few steps on a ``(pod=2, data=2,
model=1)`` mesh in ``sync`` mode and again in ``flat`` mode, and the losses
must agree.

Any failed check raises, so the exit code is non-zero and no result line is
printed.  JAX finding no TPU is a failure: there is no CPU fallback.  The
times printed are one smoke run's, not benchmark metrics.  The last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import RunConfig, ShapeConfig, get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import use_compilation_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import BatchAdmission, serve  # noqa: E402
from repro.launch.steps import build_decode_step, build_prefill_step  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models import Model, input_specs  # noqa: E402
from repro.models.attention import online_attention  # noqa: E402

ARCH = "llama3.2-1b"

# Bounds for bf16 computation (8-bit significand, unit roundoff 2^-8 ≈ 0.004).
# Decode against prefill: the same bf16 weights through two attention paths
# (full-cache softmax against blocked online softmax) and different matmul
# shapes; rounding differences add up over the residual stream, so the
# bound is on the relative RMS error of the logits, 2^-4 = 0.0625.  The
# 2-layer smoke config shows about 0.008; a decode position off by one, or a
# new key missing from the attended cache, shows 0.18 to 0.4 there.
DECODE_REL_RMS_TOL = 2.0 ** -4
# Flash kernel against online_attention: both accumulate in fp32 and round
# the output to bf16 once; inputs are unit normals, outputs are averages of
# unit-normal values, so a few bf16 ulps of an O(1) value.
KERNEL_MAX_ABS_TOL = 2.0 ** -5
# Training losses, sync against flat: identical math under two collective
# schedules; bf16 activations round differently per sharding.  The bound is
# relative to the loss, 2^-7 ≈ 0.0078 (about 0.09 at ln(128256) ≈ 11.8).
LOSS_REL_TOL = 2.0 ** -7


def require(ok, what) -> None:
    """A check that holds under ``python -O`` too (unlike ``assert``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def device_check(count: int) -> dict:
    """The device as JAX reports it; exits non-zero unless ``count`` TPUs."""
    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {d.platform!r})")
    if len(devs) < count:
        sys.exit(f"chip_smoke: need {count} TPU chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def serving_phase(*, smoke: bool, seed: int, batch: int, prompt_len: int,
                  gen_len: int, requests: int = 2) -> None:
    """``serve`` ``requests`` times through one shared admission gate."""
    vocab = get_config(ARCH, smoke=smoke).vocab_size
    admission = BatchAdmission(num_slots=1)
    first = None
    for i in range(requests):
        out = serve(ARCH, smoke=smoke, batch=batch, prompt_len=prompt_len,
                    gen_len=gen_len, seed=seed, admission=admission)
        toks, adm = out["tokens"], out["admission"]
        require(toks.shape == (batch, gen_len), f"token shape {toks.shape}")
        require(((toks >= 0) & (toks < vocab)).all(), "token out of range")
        require(out["logits_finite"], "non-finite logits")
        require(adm["local_rdma_ops"] == 0,
                f"{adm['local_rdma_ops']} local RDMA ops for admission")
        require(adm["grants"] == i + 1, f"{adm['grants']} admission grants")
        if first is None:
            first = toks
        else:  # same seed, same weights, same prompts: greedy is repeatable
            np.testing.assert_array_equal(toks, first)
        print(f"[serve] request {i}: tokens {toks.shape} in range, logits "
              f"finite, admission fence token {adm['fence_token']}, "
              f"{adm['local_rdma_ops']} local RDMA ops")
        print(f"[serve] request {i} smoke timings (not metrics): compile "
              f"{out['compile_seconds']:.3f}s, prefill "
              f"{out['prefill_seconds'] * 1e3:.2f} ms, decode "
              f"{out['decode_seconds_per_token'] * 1e3:.2f} ms/token")


def decode_check_phase(*, smoke: bool, seed: int, batch: int,
                       prompt_len: int) -> float:
    """Decode at position ``prompt_len`` against a prefill over the same
    ``prompt_len + 1`` tokens; returns the relative RMS error of the logits."""
    cfg = get_config(ARCH, smoke=smoke)
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    max_len = prompt_len + 1
    shape = lambda t, kind: ShapeConfig("check", t, batch, kind)  # noqa: E731
    with jax.set_mesh(mesh):
        prefill, _, (param_sh, batch_sh, _) = build_prefill_step(
            model, mesh, shape(prompt_len, "prefill"), max_len)
        prefill_all, _, (_, batch_all_sh, _) = build_prefill_step(
            model, mesh, shape(max_len, "prefill"), max_len)
        decode, _, (_, _, tok_sh) = build_decode_step(
            model, mesh, shape(max_len, "decode"), max_len)
        params = jax.jit(model.init, out_shardings=param_sh)(
            jax.random.PRNGKey(seed))
        toks = input_specs(cfg, shape(max_len, "prefill"), concrete=True,
                           rng=jax.random.PRNGKey(seed + 1))["tokens"]
        _, caches = prefill(
            params, jax.device_put({"tokens": toks[:, :prompt_len]}, batch_sh))
        got, _ = decode(params, caches, jax.device_put(toks[:, prompt_len:], tok_sh))
        want, _ = prefill_all(params, jax.device_put({"tokens": toks}, batch_all_sh))
    got = np.asarray(got[:, 0], np.float32)
    want = np.asarray(want[:, 0], np.float32)
    require(np.isfinite(got).all() and np.isfinite(want).all(),
            "non-finite logits")
    rel = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    print(f"[decode] decode vs prefill logits at position {prompt_len}: "
          f"relative RMS error {rel:.3e} (bound {DECODE_REL_RMS_TOL:.3e}), "
          f"max abs error {np.abs(got - want).max():.3e}")
    require(rel <= DECODE_REL_RMS_TOL, f"decode vs prefill error {rel}")
    return rel


def kernel_phase(*, seed: int, batch: int, seq_len: int, heads: int,
                 kv_heads: int, head_dim: int, q_block: int,
                 k_block: int) -> float:
    """``kernels.ops.flash_attention`` against ``online_attention``; on a TPU
    the compiled program must hold the Mosaic kernel (``tpu_custom_call``),
    which proves it was not interpreted."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (batch, seq_len, heads, head_dim), jnp.bfloat16)
    k = jax.random.normal(ks[1], (batch, seq_len, kv_heads, head_dim), jnp.bfloat16)
    v = jax.random.normal(ks[2], (batch, seq_len, kv_heads, head_dim), jnp.bfloat16)
    flash = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, True, 0, q_block, k_block, None))
    xla = jax.jit(lambda q, k, v: online_attention(
        q, k, v, causal=True, q_block=q_block, k_block=k_block))
    compiled = flash.lower(q, k, v).compile()
    on_tpu = jax.default_backend() == "tpu"
    require(("tpu_custom_call" in compiled.as_text()) == on_tpu,
            "flash kernel not compiled by Mosaic" if on_tpu else
            "Mosaic kernel off the TPU")
    got = np.asarray(compiled(q, k, v), np.float32)
    want = np.asarray(xla(q, k, v), np.float32)
    err = float(np.abs(got - want).max())
    print(f"[kernel] flash_attention B={batch} T={seq_len} H={heads} "
          f"K={kv_heads} d={head_dim} ({'Mosaic' if on_tpu else 'interpreted'})"
          f" vs online_attention: max abs error {err:.3e} "
          f"(bound {KERNEL_MAX_ABS_TOL:.3e})")
    require(err <= KERNEL_MAX_ABS_TOL, f"flash kernel error {err}")
    return err


def mesh_phase(*, smoke: bool, seed: int, steps: int, seq_len: int,
               batch: int) -> dict:
    """``train`` on a (pod=2, data=2, model=1) mesh in ``sync`` and ``flat``
    mode from one seed; the per-step losses must agree."""
    shape = ShapeConfig("smoke", seq_len=seq_len, global_batch=batch,
                        kind="train")
    losses = {}
    with tempfile.TemporaryDirectory() as ckpt_dir:
        for mode in ("sync", "flat"):
            # No checkpoint lands inside the run; warmup 1 so the last steps
            # take real optimizer updates.
            run = RunConfig(total_steps=steps, warmup_steps=1, sync_mode=mode,
                            seed=seed, checkpoint_every=steps + 1,
                            checkpoint_dir=ckpt_dir)
            t0 = time.perf_counter()
            out = train(ARCH, smoke=smoke, steps=steps, shape=shape,
                        mesh_shape=(2, 2, 1), mesh_axes=("pod", "data", "model"),
                        run=run, log_every=1)
            losses[mode] = [h["loss"] for h in out["history"]]
            del out  # drop the final train state before the next mode
            print(f"[mesh] {mode}: losses {losses[mode]} (smoke timing, not a "
                  f"metric: {time.perf_counter() - t0:.1f}s incl. compile)")
    sync, flat = np.asarray(losses["sync"]), np.asarray(losses["flat"])
    require(len(sync) == len(flat) == steps, f"losses logged: {losses}")
    require(np.isfinite(sync).all() and np.isfinite(flat).all(),
            f"non-finite loss: {losses}")
    rel = float(np.max(np.abs(sync - flat) / np.abs(flat)))
    print(f"[mesh] sync vs flat: max relative loss difference {rel:.3e} "
          f"(bound {LOSS_REL_TOL:.3e})")
    require(rel <= LOSS_REL_TOL, f"sync vs flat losses {losses}")
    return losses


def memory_report() -> None:
    """Each device's peak bytes, which must stay within its memory."""
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
        print(f"[memory] device {d.id}: peak_bytes_in_use={peak} "
              f"bytes_limit={limit}")
        if peak is not None and limit is not None:
            require(peak <= limit, f"device {d.id} peak {peak} > {limit}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip training-mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = device_check(4 if args.four_chips else 1)
    print(f"[cache] compilation cache: {use_compilation_cache()}")
    if args.four_chips:
        mesh_phase(smoke=False, seed=args.seed, steps=3, seq_len=1024, batch=8)
    else:
        cfg = get_config(ARCH)
        serving_phase(smoke=False, seed=args.seed, batch=8, prompt_len=512,
                      gen_len=32)
        decode_check_phase(smoke=False, seed=args.seed, batch=2, prompt_len=512)
        kernel_phase(seed=args.seed, batch=1, seq_len=2048,
                     heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                     head_dim=cfg.resolved_head_dim, q_block=cfg.q_block,
                     k_block=cfg.k_block)
    memory_report()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
