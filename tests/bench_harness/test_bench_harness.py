"""CPU tests of the benchmark harness at smoke sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness

They cover the reduction from a trace to metrics, the FLOP and byte counts,
each reference against the program, a cell added by files alone, the
refusal to print a result without a TPU, and the check failing when the
timed path is broken underneath or the fp8 control stands in its place.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402
from bench import spec, trace as tr  # noqa: E402
from bench.refops import make_weights  # noqa: E402
from bench.serve import program_config  # noqa: E402

DATA = ROOT / "bench" / "tests" / "data"

TINY_GQA = {
    "name": "tiny-gqa", "source": "test", "family": "dense_gqa",
    "program_arch": "glm4-9b", "num_layers": 2, "d_model": 64, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "d_ff": 96, "vocab_size": 512,
    "rope_theta": 10000.0, "norm_eps": 1e-6, "dtype": "bfloat16",
    "q_block": 16, "k_block": 16,
}
TINY_XLSTM = {
    "name": "tiny-xlstm", "source": "test", "family": "xlstm",
    "program_arch": "xlstm-1.3b", "num_layers": 2, "d_model": 64, "num_heads": 4,
    "vocab_size": 256, "block_pattern": ["mlstm", "slstm"],
    "proj_factor_m": 2.0, "proj_factor_s": 1.3333333333333333,
    "qk_dim_factor": 0.5, "norm_eps": 1e-6, "dtype": "bfloat16",
}
TINY_TRAFFIC = {
    "kind": "serve", "batch": 2, "prompt_len": 24, "gen_len": 10,
    "check_requests": 2, "trace_batches": 1,
}
# A smoke size per reference family at which the fp8 control and the
# program lie on either side of a committed cell's limits, as they do on
# the chip at the cell's own size: deep enough, and with enough vocabulary
# and checked positions, for the control's widest gap to open up.
SMOKE_CONTROL = {
    "dense_gqa": (dict(TINY_GQA, num_layers=8, vocab_size=4096),
                  dict(TINY_TRAFFIC, batch=4, gen_len=96, check_requests=4)),
}


def make_root(tmp_path: Path, cfg: dict, traffic: dict = TINY_TRAFFIC,
              limits: Optional[dict] = None, extra_metric: str = "") -> Path:
    """A checkout holding one new cell, defined by new files alone."""
    b = tmp_path / "bench"
    for d in ("configs", "traffic", "limits", "metrics"):
        (b / d).mkdir(parents=True, exist_ok=True)
    (b / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
    (b / "traffic" / "tiny-mix.json").write_text(json.dumps(traffic))
    wl = f"{cfg['name']}.tiny-mix"
    (b / "limits" / f"{wl}.json").write_text(
        json.dumps(limits or {"widest_gap": 1.0, "nonfinite_steps": 0}))
    per_layer = []
    if extra_metric:
        (b / "metrics" / "batches_run.py").write_text(extra_metric)
        per_layer.append({"name": "batches_run", "unit": "batches",
                          "better": "higher", "source": "host_clock",
                          "layer": "test", "moves": "tokens_per_s"})
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": cfg["name"], "source": "test",
                     "file": f"bench/configs/{cfg['name']}.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": wl, "config": cfg["name"], "traffic": "tiny-mix",
                       "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.25,
             "source": "host_clock"}
            for n, u in (("tokens_per_s", "tokens/s"), ("tpot_p95_ms", "ms"),
                         ("ttft_p95_ms", "ms"), ("setup_s", "s"))],
        "per_layer": per_layer,
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def run_tiny(root: Path, workload: str, seed: int = 3, seconds: float = 0.5,
             control: bool = False) -> dict:
    cell = spec.load_cell(root, workload)
    return bench_run.run_cell(cell, seed, seconds, False, time.perf_counter(),
                              control=control)


# ------------------------------------------------------------ trace ---
def test_trace_reduction_by_hand():
    ms = 1_000_000
    ev = {
        "spans": [("bench.batch", 0, 100 * ms), ("bench.pull", 40 * ms, 20 * ms),
                  ("bench.admit", 0, 10 * ms)],
        "modules": [("jit_prefill(7)", 10 * ms, 30 * ms),
                    ("jit_decode(9)", 60 * ms, 30 * ms),
                    ("jit_decode(9)", 200 * ms, 5 * ms)],  # outside the window
        "ops": [("fusion.1", 10 * ms, 20 * ms), ("fusion.2", 25 * ms, 15 * ms),
                ("fusion.1", 60 * ms, 30 * ms)],
    }
    red = tr.reduce_events(ev)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.06)        # [10,40] and [60,90]
    assert red["modules"] == {"jit_prefill": [pytest.approx(0.03)],
                              "jit_decode": [pytest.approx(0.03)]}
    assert red["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(0.05)]
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps["bench.admit"] == pytest.approx(0.01)   # [0,10]
    assert gaps["bench.pull"] == pytest.approx(0.02)    # [40,60]
    assert gaps["untraced host work"] == pytest.approx(0.01)  # [90,100]


def test_trace_reduction_on_recorded_trace():
    """A slice of a traced short-chat run on one v5e chip, as read_xspace
    returned it: one batch's prefill and first three decode steps, op names
    shortened by op_label.  The prefill program starts just before its
    batch's span on the trace's clock and must still count."""
    ev = json.loads((DATA / "trace_small.json").read_text())
    red = tr.reduce_events(ev)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert len(red["modules"]["jit_prefill"]) == 1
    assert len(red["modules"]["jit_decode"]) == 3
    assert all(d > 0 for d in red["modules"]["jit_decode"])
    assert len(red["breakdown"]["device_ops"]) <= 10
    assert len(red["breakdown"]["idle_gaps"]) <= 10
    busy_ops = sum(v for _, v in red["breakdown"]["device_ops"])
    assert busy_ops > 0


# ------------------------------------------------------------ counts ---
def test_dense_gqa_counts_by_hand():
    fam = spec.load_module(spec.find(ROOT, "reference", "dense_gqa", ".py"))
    cfg = dict(TINY_GQA)
    D, H, K, hd, F, V, L = 64, 4, 2, 16, 96, 512, 2
    per_tok = 2 * D * H * hd * 2 + 2 * D * K * hd * 2 + 2 * D * 2 * F + 2 * F * D
    B, P = 3, 10
    attn = sum(4 * H * hd * (t + 1) for t in range(P))
    flops, nbytes = fam.prefill_cost(cfg, B, P)
    assert flops == B * (L * (P * per_tok + attn) + 2 * D * V)
    wbytes = 2 * (L * (D * H * hd * 2 + D * K * hd * 2 + 3 * D * F + 2 * D) + D + D * V)
    assert fam.weight_bytes(cfg) == wbytes
    kv_tok = 2 * L * 2 * K * hd
    assert nbytes == wbytes + B * P * D * 2 + B * P * kv_tok
    flops, nbytes = fam.decode_cost(cfg, B, 7)
    assert flops == B * (L * (per_tok + 4 * H * hd * 8) + 2 * D * V)
    assert nbytes == wbytes + B * D * 2 + B * 9 * kv_tok


@pytest.mark.parametrize("cfg", [TINY_GQA, TINY_XLSTM], ids=["gqa", "xlstm"])
def test_weight_bytes_match_program(cfg):
    """Every weight but the embedding, against the program's own shapes."""
    from repro.models import Model

    fam = spec.load_module(spec.find(ROOT, "reference", cfg["family"], ".py"))
    shapes = Model(program_config(cfg)).param_shapes()
    total = sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                for s in jax.tree.leaves(shapes))
    table = shapes["embed"]["table"]
    assert fam.weight_bytes(cfg) == total - int(np.prod(table.shape)) * 2


def test_xlstm_counts_by_hand():
    fam = spec.load_module(spec.find(ROOT, "reference", "xlstm", ".py"))
    cfg = dict(TINY_XLSTM)
    D, H, V = 64, 4, 256
    inner, dh = 128, 32
    dqk, dff = 16, 85
    mat_m = 4 * D * inner + 2 * H * dh * (2 * dqk + dh) + 4 * inner * H + 2 * inner * D
    rec_m = 4 * H * dqk * dh
    mat_s = 8 * D * D + 6 * D * dff
    rec_s = 8 * H * (D // H) ** 2
    state = 4 * (H * (dqk * dh + dqk + 1) + 4 * D)
    assert fam.state_bytes(cfg) == state
    flops, nbytes = fam.decode_cost(cfg, 5, 100)
    assert flops == 5 * (mat_m + rec_m + mat_s + rec_s + 2 * D * V)
    assert nbytes == fam.weight_bytes(cfg) + 5 * D * 2 + 2 * 5 * state


# ------------------------------------------------------- references ---
@pytest.mark.parametrize("cfg", [TINY_GQA, TINY_XLSTM], ids=["gqa", "xlstm"])
def test_reference_matches_program(cfg):
    """In float32 the program's prefill and decode logits equal the
    reference's forward pass over the same tokens."""
    from repro.models import Model

    cfg = dict(cfg, dtype="float32")
    fam = spec.load_module(spec.find(ROOT, "reference", cfg["family"], ".py"))
    model = Model(program_config(cfg))
    w = make_weights(fam.layout(cfg), 11)
    B, P, G = 2, 20, 6
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], (B, P + G))
    logits, cache = jax.jit(model.prefill, static_argnums=2)(
        w, {"tokens": jnp.asarray(toks[:, :P], jnp.int32)}, P + G)
    got = [logits[:, 0]]
    step = jax.jit(model.decode_step)
    for t in range(P, P + G - 1):
        logits, cache = step(w, cache, jnp.asarray(toks[:, t:t + 1], jnp.int32))
        got.append(logits[:, 0])
    got = np.stack(got, 1)
    for b in range(B):
        ref = np.asarray(fam.logits(cfg, w, toks[b, :P + G - 1], P - 1))
        scale = np.abs(ref).max()
        np.testing.assert_allclose(got[b], ref, atol=2e-4 * scale, rtol=0)


# --------------------------------------------------- cells by files ---
READER = '''"""batches_run: a reader added by a file alone."""


def read(rec):
    return float(len(rec["batches"]))
'''


def test_cell_added_by_files_alone(tmp_path):
    root = make_root(tmp_path, TINY_GQA, extra_metric=READER)
    cell = spec.load_cell(root, "tiny-gqa.tiny-mix")
    assert cell["config"]["num_layers"] == 2 and cell["traffic"]["batch"] == 2
    out = run_tiny(root, "tiny-gqa.tiny-mix", seconds=1.0)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"tokens_per_s", "tpot_p95_ms",
                                   "ttft_p95_ms", "setup_s"}
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    rec_cell = dict(cell, per_layer=[{"name": "batches_run", "unit": "batches"}])
    assert spec.read_metrics(root, rec_cell["per_layer"],
                             {"batches": [1, 2]}) == {
        "batches_run": {"value": 2.0, "unit": "batches"}}


def test_xlstm_cell_runs(tmp_path):
    root = make_root(tmp_path, TINY_XLSTM)
    out = run_tiny(root, "tiny-xlstm.tiny-mix", seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["metrics"]["tokens_per_s"]["value"] > 0


# -------------------------------------------------------- no chip ---
def test_no_tpu_exits_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "glm4-9b-pp2.short-chat", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


# ---------------------------------------------- faults and control ---
def _broken_decode(monkeypatch):
    """A decode step that returns its cache unchanged."""
    from repro.launch import steps

    real = steps.build_decode_step

    def build(*a, **k):
        fn, spec_, sh = real(*a, **k)
        param_sh, cache_sh, tok_sh = sh

        def stale(params, caches, tokens):
            logits, _ = fn(params, caches, tokens)
            return logits, caches

        return jax.jit(stale, in_shardings=sh, out_shardings=(None, cache_sh)), spec_, sh

    monkeypatch.setattr(steps, "build_decode_step", build)


def _altered_token(monkeypatch):
    """A sampler whose token is the greedy one plus one."""
    from repro.launch import serve

    @functools.partial(jax.jit, static_argnums=3)
    def off_by_one(logits, key, step, greedy):
        last = logits[:, -1]
        tok = (jnp.argmax(last, -1) + 1) % last.shape[-1]
        return tok[:, None].astype(jnp.int32), jnp.all(jnp.isfinite(logits))

    monkeypatch.setattr(serve, "_next_token", off_by_one)


@pytest.mark.parametrize("fault", [_broken_decode, _altered_token],
                         ids=["state_unchanged", "token_altered"])
@pytest.mark.parametrize("cfg", [TINY_GQA, TINY_XLSTM], ids=["gqa", "xlstm"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, cfg, fault):
    root = make_root(tmp_path, cfg,
                     limits={"widest_gap": 0.25, "nonfinite_steps": 0})
    assert run_tiny(root, f"{cfg['name']}.tiny-mix", seconds=1.0)["correct"]
    fault(monkeypatch)
    out = run_tiny(root, f"{cfg['name']}.tiny-mix", seconds=1.0)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cfg", [TINY_GQA, TINY_XLSTM], ids=["gqa", "xlstm"])
def test_fp8_control_reads_above_the_program(tmp_path, cfg):
    """The control (the reference with fp8 matmul operands in the
    program's place) departs from the float32 reference by more than the
    bf16 program does, summed over seeds."""
    traffic = dict(TINY_TRAFFIC, batch=4, gen_len=24, check_requests=4)
    root = make_root(tmp_path, cfg, traffic=traffic)
    prog = ctl = 0.0
    for seed in (5, 6, 7):
        out = run_tiny(root, f"{cfg['name']}.tiny-mix", seed=seed,
                       seconds=2.0, control=True)
        prog += out["program_widest_gap"]
        ctl += out["checks"]["widest_gap"]["value"]
    assert ctl > 3 * prog, (prog, ctl)


def _cells_with_smoke_control():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in b["configs"]}
    return [w["name"] for w in b["workloads"]
            if json.loads((ROOT / files[w["config"]]).read_text())["family"]
            in SMOKE_CONTROL]


@pytest.mark.parametrize("workload", _cells_with_smoke_control())
def test_fp8_control_is_not_correct_under_committed_limits(tmp_path, workload):
    """Under a committed cell's own limits file, a run with the fp8 control
    in the program's place reads ``correct`` false, while the program's own
    gap on the same requests stays inside the limit."""
    cell = spec.load_cell(ROOT, workload)
    limits = cell["limits"]
    cfg, traffic = SMOKE_CONTROL[cell["config"]["family"]]
    root = make_root(tmp_path, cfg, traffic=traffic, limits=limits)
    for seed in (5, 7):
        out = run_tiny(root, f"{cfg['name']}.tiny-mix", seed=seed,
                       seconds=1.0, control=True)
        assert not out["correct"], out["checks"]
        assert out["program_widest_gap"] <= limits["widest_gap"], out


# ------------------------------------------------- BENCHMARK.json ---
NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"


def test_benchmark_json_names_and_files():
    """Every name, unit and file the benchmark names exists and is well
    formed; every cell reports setup_s, another end-to-end metric and a
    per-layer metric; each per-layer metric's cells report what it moves."""
    import re

    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert re.match(NAME, m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
        spec.find(ROOT, "metrics", m["name"], ".py")
    for w in b["workloads"]:
        assert re.match(NAME, w["name"]) and w["chips"] in (1, 4)
        cell = spec.load_cell(ROOT, w["name"])
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2 and cell["per_layer"]
        assert cell["limits"].keys() == {"widest_gap", "nonfinite_steps"}
    for m in b["per_layer"]:
        for wl in m.get("workloads", cells):
            moved = e2e[m["moves"]]
            assert wl in cells and wl in moved.get("workloads", cells)
    for c in b["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
