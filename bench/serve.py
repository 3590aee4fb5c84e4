"""Serving cells: closed-loop static batches through the program's own
objects.

Set-up builds, once, what ``launch.serve.serve`` builds on every call: the
prefill and decode executables from ``launch.steps``, the sampler
``launch.serve._next_token`` compiled for both, and one
``launch.serve.BatchAdmission``.  The weights are the reference family's,
made on the device from ``--seed`` in one jitted call.  The window then
repeats, batch after batch, the order ``serve()`` runs after its set-up:
admit, prefill, sample, keepalive, then ``gen_len - 1`` times decode, sample
and pull the token to the host, with a keepalive every 8 steps, then
complete.  Sampling is greedy, which the check needs.  One server thread:
the next batch is admitted when the last one completes.

Every timestamp is the host clock at the moment a token reached the host.
With ``trace`` on, the first ``trace_batches`` batches run under the
profiler with a ``bench.*`` span around each phase.
"""

from __future__ import annotations

import contextlib
import dataclasses
import shutil
import sys
import tempfile
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import trace as tr
from bench.refops import layout_shapes, make_weights, seed_key


# serve()'s own values: a keepalive every 8 decode steps, a 30 s TTL; one
# admission slot, since one server thread admits one batch at a time.
KEEPALIVE_EVERY = 8
ADMISSION_TTL_S = 30.0


def program_config(cfg: Dict):
    """The program's ``ModelConfig``: its registry entry with every key of
    the configuration file that names one of its fields."""
    from repro.configs import get_config

    base = get_config(cfg["program_arch"])
    fields = {f.name for f in dataclasses.fields(base)} - {"name"}
    over = {k: tuple(v) if isinstance(v, list) else v
            for k, v in cfg.items() if k in fields}
    return base.with_overrides(**over)


def prompts(traffic: Dict, vocab: int, seed: int, index: int) -> np.ndarray:
    """Batch ``index``'s prompts: ``batch`` rows of ``prompt_len`` token ids
    drawn uniformly from the vocabulary, a function of (seed, index)."""
    rng = np.random.default_rng([seed, index])
    return rng.integers(0, vocab, (traffic["batch"], traffic["prompt_len"]),
                        dtype=np.int32)


def _module_name(exe) -> str:
    return exe.runtime_executable().hlo_modules()[0].name


def run(cell: Dict, seed: int, seconds: float, trace: bool, t_process: float,
        control: bool = False) -> Dict:
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import BatchAdmission, _next_token
    from repro.launch.steps import build_decode_step, build_prefill_step
    from repro.configs import ShapeConfig
    from repro.models import Model

    marks = [("imports+device", time.perf_counter())]
    cfg, traffic, fam = cell["config"], cell["traffic"], cell["family"]
    B, P, G = traffic["batch"], traffic["prompt_len"], traffic["gen_len"]
    pcfg = program_config(cfg)
    model = Model(pcfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    max_len = P + G

    with jax.set_mesh(mesh):
        prefill_fn, _, (param_sh, batch_sh, _) = build_prefill_step(
            model, mesh, ShapeConfig("bench", P, B, "prefill"), max_len)
        decode_fn, _, _ = build_decode_step(
            model, mesh, ShapeConfig("bench", max_len, B, "decode"), max_len)
        layout = fam.layout(cfg)
        want = jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype).name),
                            model.param_shapes())
        if layout_shapes(layout) != want:
            raise RuntimeError("the reference's weight layout differs from the "
                               "program's parameter shapes")
        params = jax.block_until_ready(make_weights(layout, seed, param_sh))
        marks.append(("weights", time.perf_counter()))
        key = seed_key(seed)
        feed = lambda i: jax.device_put(  # noqa: E731
            {"tokens": prompts(traffic, cfg["vocab_size"], seed, i)}, batch_sh)

        prefill_exe = prefill_fn.lower(params, feed(0)).compile()
        logits_info, cache_info = prefill_exe.out_info
        sample_first = _next_token.lower(logits_info, key, 0, True).compile()
        decode_exe = decode_fn.lower(
            params, cache_info, sample_first.out_info[0]).compile()
        sample_next = _next_token.lower(
            decode_exe.out_info[0], key, 0, True).compile()
        admission = BatchAdmission(num_slots=1, ttl=ADMISSION_TTL_S)
        marks.append(("lower+compile", time.perf_counter()))

        # Warm-up: every executable once, and the admission path once.
        slot = admission.admit(timeout=ADMISSION_TTL_S)
        logits, caches = prefill_exe(params, feed(0))
        tok, _ = sample_first(logits, key, 0)
        slot = admission.keepalive(slot)
        for step in range(2):
            logits, caches = decode_exe(params, caches, tok)
            tok, _ = sample_next(logits, key, step + 1)
            np.asarray(tok)
        admission.complete(slot)
        del logits, caches, tok
        names = {"prefill": _module_name(prefill_exe),
                 "decode": _module_name(decode_exe)}
        setup_s = time.perf_counter() - t_process
        marks.append(("warm-up", t_process + setup_s))
        print("setup: " + ", ".join(
            f"{name} {b - a:.3f} s" for (_, a), (name, b) in
            zip([("process", t_process)] + marks[:-1], marks)), file=sys.stderr)

        batches: List[Dict] = []
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        tracing = trace
        span = (lambda n: jax.profiler.TraceAnnotation(n)) if trace else (
            lambda n: contextlib.nullcontext())
        t0 = time.perf_counter()
        deadline = t0 + seconds
        if tracing:
            jax.profiler.start_trace(trace_dir)
        while time.perf_counter() < deadline or (
                tracing and len(batches) < traffic["trace_batches"]):
            i = len(batches)
            rec = {"index": i, "prompts": prompts(traffic, cfg["vocab_size"], seed, i),
                   "times": [], "tokens": [], "finite": [], "admission_s": 0.0,
                   "finished": False}
            batches.append(rec)
            with span(tr.WINDOW_SPAN):
                ta = time.perf_counter()
                with span("bench.admit"):
                    slot = admission.admit(timeout=ADMISSION_TTL_S)
                rec["t_admit"] = ta
                rec["admission_s"] += time.perf_counter() - ta
                with span("bench.feed"):
                    batch = jax.device_put({"tokens": rec["prompts"]}, batch_sh)
                with span("bench.prefill"):
                    logits, caches = prefill_exe(params, batch)
                with span("bench.sample"):
                    tok, finite = sample_first(logits, key, 0)
                with span("bench.pull"):
                    rec["tokens"].append(np.asarray(tok))
                rec["times"].append(time.perf_counter())
                rec["finite"].append(finite)
                ta = time.perf_counter()
                with span("bench.keepalive"):
                    slot = admission.keepalive(slot)
                rec["admission_s"] += time.perf_counter() - ta
                for step in range(G - 1):
                    if time.perf_counter() >= deadline and not tracing:
                        break
                    with span("bench.decode"):
                        logits, caches = decode_exe(params, caches, tok)
                    with span("bench.sample"):
                        tok, finite = sample_next(logits, key, step + 1)
                    with span("bench.pull"):
                        rec["tokens"].append(np.asarray(tok))
                    rec["times"].append(time.perf_counter())
                    rec["finite"].append(finite)
                    if step % KEEPALIVE_EVERY == KEEPALIVE_EVERY - 1:
                        ta = time.perf_counter()
                        with span("bench.keepalive"):
                            slot = admission.keepalive(slot)
                        rec["admission_s"] += time.perf_counter() - ta
                ta = time.perf_counter()
                with span("bench.complete"):
                    admission.complete(slot)
                rec["admission_s"] += time.perf_counter() - ta
                rec["finished"] = len(rec["tokens"]) == G
            if tracing and len(batches) >= traffic["trace_batches"]:
                jax.profiler.stop_trace()
                tracing = False
        del logits, caches, tok, batch

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices())
        trace_red = None
        if trace:
            trace_red = tr.reduce_events(tr.read_xspace(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)

        for r in batches:
            r["tokens"] = np.concatenate(r["tokens"], axis=1)
            r["nonfinite"] = sum(not bool(f) for f in r.pop("finite"))
        checks, program_gap = _check(cell, params, batches, seed, control)
        checks["nonfinite_steps"] = sum(r["nonfinite"] for r in batches)

    return {
        "kind": "serve", "config": cfg, "traffic": traffic, "family": fam,
        "setup_s": setup_s, "seconds": seconds, "t0": t0,
        "batches": batches, "memory_peak_bytes": peak, "trace": trace_red,
        "module_names": names, "checks": checks, "program_gap": program_gap,
        "attempted": B * len(batches),
        "failed": B * sum(1 for r in batches if r["nonfinite"]),
    }


def _gaps(ref, served):
    """Per position: how far the served token's reference logit lies below
    the reference's best."""
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]


def _check(cell: Dict, params, batches: List[Dict], seed: int, control: bool):
    """The widest gap over a seeded sample of finished requests.  With
    ``control`` the fp8 control's gap over the same positions takes the
    program's place in the checks, and the program's own is returned beside
    them."""
    cfg, traffic, fam = cell["config"], cell["traffic"], cell["family"]
    P = traffic["prompt_len"]
    done = [(r["index"], row) for r in batches if r["finished"]
            for row in range(traffic["batch"])]
    if not done:
        raise RuntimeError("no request finished in the window")
    rng = np.random.default_rng([seed, 0x5EED])
    pick = rng.choice(len(done), min(traffic["check_requests"], len(done)),
                      replace=False)
    widest, widest_ctl = 0.0, 0.0
    for j in sorted(pick):
        b, row = done[j]
        r = batches[b]
        served = r["tokens"][row]
        seq = np.concatenate([r["prompts"][row], served[:-1]])
        ref = fam.logits(cfg, params, seq, P - 1)
        sv = jnp.asarray(served, jnp.int32)
        widest = max(widest, float(jnp.max(_gaps(ref, sv))))
        if control:
            ctl = fam.logits(cfg, params, seq, P - 1, quant=True)
            widest_ctl = max(widest_ctl, float(jnp.max(
                _gaps(ref, jnp.argmax(ctl, -1).astype(jnp.int32)))))
        del ref
    if control:
        return {"widest_gap": widest_ctl}, widest
    return {"widest_gap": widest}, None
