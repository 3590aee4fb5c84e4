"""End-to-end behaviour: loss decreases, checkpoints resume deterministically,
serving generates; multi-device training equivalences run in subprocesses."""

import time

import numpy as np
import pytest

from repro import obs
from repro.configs import RunConfig, ShapeConfig
from repro.launch.serve import BatchAdmission, serve
from repro.launch.train import train


def test_training_reduces_loss(tmp_path):
    run = RunConfig(
        learning_rate=5e-3, warmup_steps=5, total_steps=80,
        checkpoint_every=1000, checkpoint_dir=str(tmp_path),
    )
    out = train(
        "llama3.2-1b", smoke=True, steps=80,
        shape=ShapeConfig("e2e", seq_len=64, global_batch=8, kind="train"),
        run=run, log_every=10,
    )
    losses = [h["loss"] for h in out["history"]]
    assert losses[-1] < losses[0] - 0.5, f"no learning: {losses}"


def test_resume_is_deterministic(tmp_path):
    shape = ShapeConfig("e2e", seq_len=32, global_batch=4, kind="train")

    def mk_run(d):
        return RunConfig(learning_rate=5e-4, warmup_steps=2, total_steps=20,
                         checkpoint_every=10, checkpoint_dir=str(d))

    # uninterrupted 20 steps
    full = train("llama3.2-1b", steps=20, shape=shape, run=mk_run(tmp_path / "a"),
                 log_every=20)
    # interrupted at 10, resumed to 20
    train("llama3.2-1b", steps=10, shape=shape, run=mk_run(tmp_path / "b"),
          log_every=20)
    resumed = train("llama3.2-1b", steps=20, shape=shape,
                    run=mk_run(tmp_path / "b"), resume=True, log_every=20)
    a = full["history"][-1]["loss"]
    b = resumed["history"][-1]["loss"]
    assert abs(a - b) < 2e-3, f"resume diverged: {a} vs {b}"


def test_serving_generates_tokens():
    out = serve("llama3.2-1b", smoke=True, batch=2, prompt_len=16, gen_len=8)
    assert out["tokens"].shape == (2, 8)
    assert out["tokens"].dtype.kind == "i"


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_serving_compiles_nothing_after_admission(greedy):
    """Every program of the batch compiles before the slot is taken: a
    compile inside the lease would be charged to the slot TTL and to the
    prefill/decode clocks."""
    class Admission(BatchAdmission):
        def admit(self, *a, **k):
            admitted.append(time.perf_counter_ns())
            return super().admit(*a, **k)

    admitted = []
    t = time.perf_counter_ns()
    out = serve("llama3.2-1b", smoke=True, batch=2, prompt_len=16,
                gen_len=10, greedy=greedy, admission=Admission(num_slots=1))
    # Each compile's record, made as the compile ends: before the admission
    # or after it.
    compiles = [c.end_ns > admitted[0]
                for c in obs.spans(obs.COMPILE_SPAN, since_ns=t)]
    assert out["tokens"].shape == (2, 10) and out["logits_finite"]
    assert admitted and compiles.count(False) >= 2  # prefill, decode
    assert compiles.count(True) == 0


def test_moe_arch_trains(tmp_path):
    run = RunConfig(learning_rate=1e-3, warmup_steps=2, total_steps=20,
                    checkpoint_every=1000, checkpoint_dir=str(tmp_path))
    out = train(
        "deepseek-v2-236b", smoke=True, steps=20,
        shape=ShapeConfig("e2e", seq_len=32, global_batch=4, kind="train"),
        run=run, log_every=4,
    )
    losses = [h["loss"] for h in out["history"]]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_sync_equals_flat_on_multipod_mesh(multidevice):
    """Cohort schedule (sync) must be numerically identical to the flat
    paper-baseline; budgeted local mode must diverge only between syncs."""
    out = multidevice(
        """
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh
from repro.configs import get_config, ShapeConfig, RunConfig
from repro.models import Model, input_specs
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_train_step, init_train_state

res = {}
for mode in ['flat', 'sync']:
    mesh = make_mesh((2,2,2), ('pod','data','model'))
    cfg = get_config('llama3.2-1b', smoke=True).with_overrides(dtype='float32')
    run = RunConfig(sync_mode=mode, total_steps=10)
    model = Model(cfg)
    shp = ShapeConfig('t', 32, 4, 'train')
    with set_mesh(mesh):
        step, shapes, state_sh, batch_sh = build_train_step(model, run, mesh, shp)
        state = jax.device_put(init_train_state(model, run, jax.random.PRNGKey(0), 2), state_sh)
        batch = jax.device_put(input_specs(cfg, shp, concrete=True, dtype=jnp.float32), batch_sh)
        ls = []
        for i in range(3):
            state, metrics = step(state, batch)
            ls.append(float(metrics['loss']))
    res[mode] = ls
# identical math, different collective schedules: equal to fp32 tolerance
np.testing.assert_allclose(res['flat'], res['sync'], rtol=1e-5)
print('OK', res)
""",
        devices=8,
    )
    assert "OK" in out


@pytest.mark.slow
def test_int8_compressed_sync_close_to_exact(multidevice):
    out = multidevice(
        """
import jax, jax.numpy as jnp
from jax import set_mesh
from repro.configs import get_config, ShapeConfig, RunConfig
from repro.models import Model, input_specs
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_train_step, init_train_state

res = {}
for mode, extra in [('sync', {}), ('sync', {'compress_int8': True})]:
    mesh = make_mesh((2,2,2), ('pod','data','model'))
    cfg = get_config('llama3.2-1b', smoke=True)
    run = RunConfig(sync_mode=mode, total_steps=10, **extra)
    model = Model(cfg)
    shp = ShapeConfig('t', 32, 4, 'train')
    with set_mesh(mesh):
        step, shapes, state_sh, batch_sh = build_train_step(model, run, mesh, shp)
        state = jax.device_put(init_train_state(model, run, jax.random.PRNGKey(0), 2), state_sh)
        batch = jax.device_put(input_specs(cfg, shp, concrete=True), batch_sh)
        for i in range(3):
            state, metrics = step(state, batch)
    res['int8' if extra else 'exact'] = float(metrics['loss'])
diff = abs(res['int8'] - res['exact'])
assert diff < 5e-3, res
print('OK', res)
""",
        devices=8,
    )
    assert "OK" in out


@pytest.mark.slow
def test_microbatched_grads_match_full_batch(multidevice):
    out = multidevice(
        """
import jax, jax.numpy as jnp
from jax import set_mesh
from repro.configs import get_config, ShapeConfig, RunConfig
from repro.models import Model, input_specs
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_train_step, init_train_state

res = {}
for mb in [1, 4]:
    mesh = make_mesh((2,2), ('data','model'))
    cfg = get_config('llama3.2-1b', smoke=True).with_overrides(dtype='float32')
    run = RunConfig(sync_mode='flat', total_steps=10, microbatches=mb)
    model = Model(cfg)
    shp = ShapeConfig('t', 32, 8, 'train')
    with set_mesh(mesh):
        step, shapes, state_sh, batch_sh = build_train_step(model, run, mesh, shp)
        state = jax.device_put(init_train_state(model, run, jax.random.PRNGKey(0)), state_sh)
        batch = jax.device_put(input_specs(cfg, shp, concrete=True, dtype=jnp.float32), batch_sh)
        state, metrics = step(state, batch)
    res[mb] = float(metrics['grad_norm'])
assert abs(res[1] - res[4]) / res[1] < 1e-3, res
print('OK', res)
""",
        devices=4,
    )
    assert "OK" in out
