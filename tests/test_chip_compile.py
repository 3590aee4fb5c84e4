"""Bring-up guards for the TPU path, at no chip time.

* Compiles for a described (not attached) v5e chip: the Pallas flash kernel
  at the head dims the models use, llama3.2-1b's full-width prefill and
  decode steps, GLM-4-9B's decode step at the long-context shape, and
  DeepSeek-V2-Lite's chip share's decode step at the moe-decode shape.  The
  topology is described inside a fixture, never while a module is
  imported, so every pytest-xdist worker collects the same tests.
* ``chip_smoke.py``'s phase functions at the smoke config on the CPU; only
  its device check, which refuses anything but a TPU, is left out.
* Where the persistent compilation cache goes.
"""

import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.configs import ShapeConfig, get_config  # noqa: E402
from repro.kernels.flash_attention import flash_attention_fwd  # noqa: E402
from repro.launch.compile_cache import CHECKOUT_CACHE, use_compilation_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.steps import build_decode_step, build_prefill_step  # noqa: E402
from repro.models import Model, input_specs  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def llama_serving(topo):
    """Full-width llama3.2-1b on a one-chip mesh of the described topology,
    with the chip_smoke serving shape (batch 8, prompt 512, 32 generated)."""
    model = Model(get_config("llama3.2-1b"))
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    return model, mesh, 8, 512, 512 + 32


def _on(tree, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


@pytest.mark.parametrize("H,K,T,dk,dv", [
    (32, 8, 2048, 64, 64),     # llama3.2-1b
    (32, 8, 2048, 128, 128),   # llama3-8b
    (16, 16, 1024, 192, 128),  # MLA: nope 128 + rope 64 keys, 128 values
], ids=["d64", "d128", "mla"])
def test_flash_kernel_compiles_for_v5e(one_chip, H, K, T, dk, dv):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,  # noqa: E731
                                            sharding=one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention_fwd(
        q, k, v, q_block=512, k_block=1024, interpret=False))
    compiled = fn.lower(s(1, H, T, dk), s(1, K, T, dk), s(1, K, T, dv)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_llama_prefill_compiles_for_v5e(llama_serving):
    model, mesh, batch, prompt_len, max_len = llama_serving
    shape = ShapeConfig("serve", prompt_len, batch, "prefill")
    with jax.set_mesh(mesh):
        step, _, (param_sh, batch_sh, _) = build_prefill_step(
            model, mesh, shape, max_len)
        compiled = step.lower(_on(model.param_shapes(), param_sh),
                              _on(input_specs(model.cfg, shape), batch_sh)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_llama_decode_compiles_for_v5e(llama_serving):
    model, mesh, batch, _, max_len = llama_serving
    shape = ShapeConfig("serve", max_len, batch, "decode")
    with jax.set_mesh(mesh):
        step, cache_spec, (param_sh, cache_sh, tok_sh) = build_decode_step(
            model, mesh, shape, max_len)
        compiled = step.lower(
            _on(model.param_shapes(), param_sh), _on(cache_spec, cache_sh),
            jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=tok_sh),
        ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _cache_buffers_moved(text, dims):
    """Unfused ops that yield a bf16 buffer of one of the shapes ``dims``
    by copying it, slicing it out, or updating it anywhere but after the
    layer scan.  What may remain is the step's row write: a dynamic update
    of the donated cache in the entry computation."""
    moved, comp = [], ""
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            comp = line.split("(", 1)[0]
            continue
        m = re.search(r"%(\S+) = bf16\[([\d,]+)\]\S* ([\w-]+)\(", line)
        if (not m or m.group(2) not in dims or "fus" in comp
                or m.group(3) not in ("copy", "copy-start", "dynamic-slice",
                                      "dynamic-update-slice", "fusion")):
            continue
        row_write = comp.startswith("ENTRY") and "dynamic-update-slice" in (
            m.group(1) + " " + m.group(3))
        if not row_write:
            moved.append(line.strip()[:160])
    return moved


def test_glm4_longctx_decode_never_widens_cache(topo):
    """GLM-4-9B (32 query heads over 2 KV heads, head 128, every published
    width; two layers to keep the compile short) at the longctx cell's
    decode shape, batch 4 over a 4352-slot cache, on one chip: decode
    attention reads the [B, S, K, d] cache as stored, so no [B, S, H, d]
    array exists and the step's temporaries stay small (widened: 367 MB);
    the layer scan only reads the stacked K and V, so neither is copied
    whole (a copy each when each layer's update passed through the scan)."""
    model = Model(get_config("glm4-9b").with_overrides(num_layers=2))
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    batch, max_len = 4, 4352
    shape = ShapeConfig("serve", max_len, batch, "decode")
    with jax.set_mesh(mesh):
        step, cache_spec, (param_sh, cache_sh, tok_sh) = build_decode_step(
            model, mesh, shape, max_len)
        compiled = step.lower(
            _on(model.param_shapes(), param_sh), _on(cache_spec, cache_sh),
            jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=tok_sh),
        ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6
    cfg = model.cfg
    widened = batch * max_len * cfg.num_heads * cfg.resolved_head_dim
    text = compiled.as_text()
    shapes = set(re.findall(r"\w+\[([\d,]+)\]", text))
    assert not [s for s in shapes
                if math.prod(int(n) for n in s.split(",")) == widened]
    stacked = f"2,{batch},{max_len},{cfg.num_kv_heads},{cfg.resolved_head_dim}"
    assert not _cache_buffers_moved(text, {stacked})


def test_deepseek_v2_lite_decode_reads_held_experts_in_place(topo):
    """DeepSeek-V2-Lite's chip share (experts 0-15 of 64, every published
    width, all 27 layers) at the moe-decode cell's decode shape, batch 32
    over a 1280-slot latent cache, on one chip: the expert layer takes its
    dense form there, so no layer's held experts are copied out of the
    scanned stack for a grouped product's custom call (measured on a TPU
    v5e: 21.6 ms of a 50 ms step).  The latent cache is read in place too:
    no layer's [32, 1280, 512] slab and no copy of the 26 scanned layers'
    stack is made (6.2 ms of a 21.4 ms step when each layer's update passed
    through the scan), and the temporaries stay small (then 1.23 GB)."""
    cfg = get_config("deepseek-v2-lite").with_overrides(moe={"num_held": 16})
    model = Model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    batch, max_len = 32, 1280
    shape = ShapeConfig("serve", max_len, batch, "decode")
    with jax.set_mesh(mesh):
        step, cache_spec, (param_sh, cache_sh, tok_sh) = build_decode_step(
            model, mesh, shape, max_len)
        compiled = step.lower(
            _on(model.param_shapes(), param_sh), _on(cache_spec, cache_sh),
            jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=tok_sh),
        ).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    copied, fused = [], False  # a layer's experts as a buffer of their own
    for line in text.splitlines():
        if line and not line[0].isspace():
            fused = "fused" in line.split("(", 1)[0]
        elif not fused and re.search(
                r"= bf16\[16,(?:2048,2816|1408,2048)\]\S* (?:fusion|copy)\(", line):
            copied.append(line)
    assert not copied
    latent = {f"{lead}{batch},{max_len},{w}" for lead in ("", "26,")
              for w in (512, 64)}
    assert not _cache_buffers_moved(text, latent)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2e9
    assert temp < 64e6


def test_chip_smoke_phases_on_cpu():
    chip_smoke.serving_phase(smoke=True, seed=0, batch=2, prompt_len=16,
                             gen_len=8)
    chip_smoke.decode_check_phase(smoke=True, seed=0, batch=2, prompt_len=16)
    cfg = get_config("llama3.2-1b", smoke=True)
    chip_smoke.kernel_phase(seed=0, batch=1, seq_len=64, heads=cfg.num_heads,
                            kv_heads=cfg.num_kv_heads,
                            head_dim=cfg.resolved_head_dim,
                            q_block=cfg.q_block, k_block=cfg.k_block)


def test_chip_smoke_mesh_phase_on_cpu(multidevice):
    out = multidevice(
        f"""
import sys
sys.path.insert(0, {REPO!r})
import chip_smoke
chip_smoke.mesh_phase(smoke=True, seed=0, steps=3, seq_len=32, batch=8)
chip_smoke.memory_report()
print('OK')
""",
        devices=4,
    )
    assert "OK" in out


def test_compilation_cache_placed_from_outside(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, entries land there and nothing is
    written to the checkout's cache."""
    placed = tmp_path / "placed"
    before = sorted(os.listdir(CHECKOUT_CACHE)) if CHECKOUT_CACHE.exists() else None
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(placed),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(REPO, "src"))
    code = ("import jax\n"
            "from repro.launch.compile_cache import use_compilation_cache\n"
            "print(use_compilation_cache())\n"
            "jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(1.0))\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(placed)
    assert any(placed.iterdir())
    after = sorted(os.listdir(CHECKOUT_CACHE)) if CHECKOUT_CACHE.exists() else None
    assert after == before


def test_compilation_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert use_compilation_cache() == str(CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
        assert CHECKOUT_CACHE == CHECKOUT_CACHE.parent / ".jax_cache"
        assert (CHECKOUT_CACHE.parent / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
