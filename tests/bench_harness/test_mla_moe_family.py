"""The ``mla_moe`` reference family (latent attention, one chip's share of
routed experts) and the ``deepseek-v2-lite-ep4`` configuration, at tiny
sizes on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench.refops import layout_shapes, make_weights  # noqa: E402
from bench.serve import program_config  # noqa: E402
from test_bench_harness import (  # noqa: E402
    TINY_TRAFFIC, _altered_token, _broken_decode, make_root, run_tiny)

FAM = spec.load_module(spec.find(ROOT, "reference", "mla_moe", ".py"))
COMMITTED = json.loads((ROOT / "bench/configs/deepseek-v2-lite-ep4.json").read_text())

# The reference reads DeepSeek-V2's config.json keys; the program reads its
# registry entry, overridden by the keys that name its config's fields.
# This chip holds experts 2-5 of the 8 the router scores.
TINY = {
    "name": "tiny-mla-moe", "source": "test", "family": "mla_moe",
    "program_arch": "deepseek-v2-lite",
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_shared_experts": 2, "n_routed_experts": 4, "num_experts_per_tok": 3,
    "norm_topk_prob": False, "scoring_func": "softmax", "topk_method": "greedy",
    "routed_scaling_factor": 1, "vocab_size": 512, "rope_theta": 10000,
    "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "dtype": "bfloat16",
    "num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
    "d_ff": 96,
    "mla": {"kv_lora_rank": 32, "rope_head_dim": 8, "nope_head_dim": 16,
            "v_head_dim": 16},
    "moe": {"num_experts": 8, "top_k": 3, "d_expert": 32, "dense_d_ff": 96,
            "first_held": 2, "num_held": 4},
    "yarn": {"original_max_position_embeddings": 16},
    "q_block": 16, "k_block": 16,
}


def _program_shapes(cfg):
    from repro.models import Model

    return jax.tree.map(lambda s: (tuple(s.shape), jnp.dtype(s.dtype).name),
                        Model(program_config(cfg)).param_shapes())


def test_layout_equals_the_programs_shapes_at_full_size():
    """Shapes only: the committed configuration's reference layout is the
    program's parameter tree (16 held experts, a router over 64), and its
    weights are 4.91 B parameters."""
    layout = FAM.layout(COMMITTED)
    assert layout_shapes(layout) == _program_shapes(COMMITTED)
    assert layout_shapes(FAM.layout(TINY)) == _program_shapes(TINY)
    n = sum(int(np.prod(s)) for s, _ in jax.tree.leaves(
        layout_shapes(layout), is_leaf=lambda x: isinstance(x, tuple)
        and len(x) == 2 and isinstance(x[1], str)))
    assert 4.90e9 < n < 4.92e9, n


def test_weight_bytes_match_program():
    from repro.models import Model

    for cfg in (TINY, COMMITTED):
        shapes = Model(program_config(cfg)).param_shapes()
        total = sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                    for s in jax.tree.leaves(shapes))
        table = shapes["embed"]["table"]
        assert FAM.weight_bytes(cfg) == total - int(np.prod(table.shape)) * 2


def test_counts_by_hand():
    D, H, r, dn, dr, dv, F, Fe, V = 64, 4, 32, 16, 8, 16, 96, 32, 512
    routed, held, k, shared, L = 8, 4, 3, 2, 3
    attn_w = D * H * (dn + dr) + D * (r + dr) + r + r * H * (dn + dv) + H * dv * D
    bf16 = (L * (attn_w + 2 * D) + 3 * D * F + 2 * (held + shared) * 3 * D * Fe
            + D + D * V)
    wbytes = 2 * bf16 + 4 * 2 * D * routed
    assert FAM.weight_bytes(TINY) == wbytes
    proj = 2 * (D * H * (dn + dr) + D * (r + dr) + H * dv * D)
    dense = proj + 6 * D * F
    moe = proj + 2 * D * routed + 6 * D * shared * Fe + (k * held / routed) * 6 * D * Fe
    B, P = 3, 10
    attn = 2 * H * (dn + dr + dv) * P * (P + 1) // 2
    expand = 2 * r * H * (dn + dv)
    flops, nbytes = FAM.prefill_cost(TINY, B, P)
    assert flops == pytest.approx(
        B * (P * dense + 2 * P * moe + L * (P * expand + attn) + 2 * D * V))
    lat = 2 * L * (r + dr)
    assert nbytes == wbytes + B * P * D * 2 + B * P * lat
    ctx = 7
    flops, nbytes = FAM.decode_cost(TINY, B, ctx)
    absorb = 2 * H * r * (dn + dv)
    att = 2 * H * (2 * r + dr) * (ctx + 1)
    assert flops == pytest.approx(
        B * (dense + 2 * moe + L * (absorb + att) + 2 * D * V))
    experts = 2 * 2 * held * 3 * D * Fe
    hit = 1 - (5 / 8) ** B
    assert nbytes == pytest.approx(wbytes - (1 - hit) * experts + B * D * 2
                                   + B * (ctx + 2) * lat)
    # The committed cell: 95.7 % of the held experts read at batch 32.
    m = FAM.dims(COMMITTED)
    assert 1 - (58 / 64) ** 32 == pytest.approx(0.957, abs=5e-4)
    assert (m.routed, m.held, m.top_k) == (64, 16, 6)


def test_reference_matches_program():
    """In float32 the program's prefill and decode logits equal the
    reference's forward pass over the same tokens.  Tolerance 2e-4 of the
    largest logit: both compute in float32, the program with blocked
    online softmax, absorbed decode and grouped expert products, the
    reference with plain softmax, expanded keys and dense experts, so they
    differ only in summation order (a few ulps per layer)."""
    from repro.models import Model

    cfg = dict(TINY, dtype="float32")
    model = Model(program_config(cfg))
    w = make_weights(FAM.layout(cfg), 11)
    B, P, G = 2, 136, 6  # the prefill's 272 tokens take the grouped products
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
        ref = np.asarray(FAM.logits(cfg, w, toks[b, :P + G - 1], P - 1))
        scale = np.abs(ref).max()
        np.testing.assert_allclose(got[b], ref, atol=2e-4 * scale, rtol=0)


def test_tiny_cell_runs_from_files(tmp_path):
    root = make_root(tmp_path, TINY)
    out = run_tiny(root, "tiny-mla-moe.tiny-mix", seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert out["attempted"] >= 2 and out["failed"] == 0


def test_fp8_control_reads_above_the_program(tmp_path):
    traffic = dict(TINY_TRAFFIC, batch=4, gen_len=24, check_requests=4)
    root = make_root(tmp_path, TINY, traffic=traffic)
    prog = ctl = 0.0
    for seed in (5, 6, 7):
        out = run_tiny(root, "tiny-mla-moe.tiny-mix", seed=seed, seconds=2.0,
                       control=True)
        prog += out["program_widest_gap"]
        ctl += out["checks"]["widest_gap"]["value"]
    assert ctl > 3 * prog, (prog, ctl)


@pytest.mark.parametrize("fault", [_broken_decode, _altered_token],
                         ids=["state_unchanged", "token_altered"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    root = make_root(tmp_path, TINY,
                     limits={"widest_gap": 0.25, "nonfinite_steps": 0})
    assert run_tiny(root, "tiny-mla-moe.tiny-mix", seconds=1.0)["correct"]
    fault(monkeypatch)
    out = run_tiny(root, "tiny-mla-moe.tiny-mix", seconds=1.0)
    assert not out["correct"], out["checks"]
