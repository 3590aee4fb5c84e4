"""The per-layer metrics that read the program's own spans and counters
(``repro.obs``), on a tiny GQA cell run on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import serve as bench_serve, spec  # noqa: E402

TINY_GQA = {
    "name": "tiny-gqa", "source": "test", "family": "dense_gqa",
    "program_arch": "glm4-9b", "num_layers": 2, "d_model": 64, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "d_ff": 96, "vocab_size": 512,
    "rope_theta": 10000.0, "norm_eps": 1e-6, "dtype": "bfloat16",
    "q_block": 16, "k_block": 16,
}
TRAFFIC = {"kind": "serve", "batch": 2, "prompt_len": 24, "gen_len": 10,
           "check_requests": 1, "trace_batches": 1}
NEW = ("admission_span_us_per_batch", "admission_table_ops_per_batch",
       "compiles_in_window")


@pytest.fixture(scope="module")
def metrics():
    cell = {"config": TINY_GQA, "traffic": TRAFFIC,
            "family": spec.load_module(spec.find(ROOT, "reference",
                                                 "dense_gqa", ".py"))}
    rec = bench_serve.run(cell, seed=11, seconds=1.5, trace=False,
                          t_process=time.perf_counter())
    entries = [m for m in json.loads((ROOT / "BENCHMARK.json").read_text())
               ["per_layer"] if m["name"] in NEW + ("admission_us_per_batch",)]
    return rec, spec.read_metrics(ROOT, entries, rec)


def test_every_new_metric_reads(metrics):
    rec, out = metrics
    assert set(NEW) <= set(out), out
    assert sum(b["finished"] for b in rec["batches"]) >= 2


def test_no_compile_inside_the_window(metrics):
    assert metrics[1]["compiles_in_window"]["value"] == 0


def test_admission_spans_lie_inside_the_loop_timing(metrics):
    """The program's spans sit inside the calls the benchmark's loop times,
    so they read no more than the loop's own admission time per batch."""
    out = metrics[1]
    assert 0 < out["admission_span_us_per_batch"]["value"] <= \
        out["admission_us_per_batch"]["value"]


def test_table_ops_per_batch_is_the_local_path(metrics):
    """One admit, a keepalive after the prefill and every 8 decode steps,
    one complete: a whole number of local ops, the same in every batch."""
    value = metrics[1]["admission_table_ops_per_batch"]["value"]
    assert value > 0 and value == int(value)
