"""Elastic scaling: checkpoints are mesh-agnostic — train on mesh A, lose
devices, restore and continue on mesh B (deliverable: fault tolerance)."""

import pytest


@pytest.mark.slow
def test_remesh_restore_preserves_state(multidevice, tmp_path):
    out = multidevice(
        f"""
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh
from repro.configs import get_config, ShapeConfig, RunConfig
from repro.models import Model, input_specs
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_train_step, init_train_state
from repro.checkpoint import save_checkpoint, load_checkpoint

ckpt_dir = {str(tmp_path)!r}
cfg = get_config('llama3.2-1b', smoke=True).with_overrides(dtype='float32')
run = RunConfig(sync_mode='flat', total_steps=20)
shp = ShapeConfig('t', 32, 8, 'train')
model = Model(cfg)

# Phase 1: train 2 steps on an 8-device (4, 2) mesh, checkpoint.
mesh_a = make_mesh((4, 2), ('data', 'model'))
with set_mesh(mesh_a):
    step, shapes, sh_a, bsh_a = build_train_step(model, run, mesh_a, shp)
    state = jax.device_put(init_train_state(model, run, jax.random.PRNGKey(0)), sh_a)
    batch = jax.device_put(input_specs(cfg, shp, concrete=True, dtype=jnp.float32), bsh_a)
    for _ in range(2):
        state, m1 = step(state, batch)
    host_state = jax.tree.map(np.asarray, state)
    save_checkpoint(ckpt_dir, 2, host_state)
    state, m_ref = step(state, batch)
    ref_loss = float(m_ref['loss'])

# Phase 2: "lose half the fleet" — restore on a (2, 2) mesh and continue.
mesh_b = make_mesh((2, 2), ('data', 'model'), devices=jax.devices()[:4])
with set_mesh(mesh_b):
    step_b, shapes_b, sh_b, bsh_b = build_train_step(model, run, mesh_b, shp)
    restored, step_no, _ = load_checkpoint(ckpt_dir, shapes_b, shardings=sh_b)
    batch_b = jax.device_put(input_specs(cfg, shp, concrete=True, dtype=jnp.float32), bsh_b)
    restored, m2 = step_b(restored, batch_b)
new_loss = float(m2['loss'])
assert step_no == 2
assert abs(new_loss - ref_loss) < 1e-4, (new_loss, ref_loss)
print('OK remesh', ref_loss, new_loss)
""",
        devices=8,
    )
    assert "OK remesh" in out
