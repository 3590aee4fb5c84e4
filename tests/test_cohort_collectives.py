"""Cohort collectives (the paper's technique on the TPU fabric)."""

import pytest

from repro.core.asymmetry import TPUv5e, cohort_vs_flat_dcn_bytes


def test_cost_model_headline_numbers():
    """The napkin math quoted in DESIGN.md/EXPERIMENTS.md."""
    r = cohort_vs_flat_dcn_bytes(16.1e9, pods=2, chips_per_pod=256)
    # ratio = [2(n-1)/n] / [2(p-1)/p / chips] ≈ 2 × cohort size at p=2
    assert 500 < r["reduction"] < 520
    hw = TPUv5e()
    flat_s = r["flat_dcn_bytes_per_chip"] / hw.dcn_bw_per_chip
    coh_s = r["cohort_dcn_bytes_per_chip"] / hw.dcn_bw_per_chip
    assert coh_s < flat_s / 200


@pytest.mark.slow
def test_cohort_all_reduce_equals_flat(multidevice):
    out = multidevice(
        """
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh
from repro.launch.mesh import make_mesh
from repro.core import cohort_all_reduce, flat_all_reduce
mesh = make_mesh((2,2,2), ('pod','data','model'))
tree = {'w': jnp.arange(24, dtype=jnp.float32).reshape(4,6),
        'b': jnp.ones((3,))*0.5}
with set_mesh(mesh):
    a = cohort_all_reduce(tree, mesh)
    b = flat_all_reduce(tree, mesh)
for k in tree:
    np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a[k]), np.asarray(tree[k])*4, rtol=1e-6)
print('OK cohort')
""",
        devices=8,
    )
    assert "OK cohort" in out


@pytest.mark.slow
def test_int8_error_feedback_converges(multidevice):
    """Error feedback: repeated compressed exchanges of the SAME gradient
    must converge to the true mean (the residual is carried, not lost)."""
    out = multidevice(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import set_mesh, shard_map
from repro.launch.mesh import make_mesh
from repro.core.cohort import pod_sync_grads, SyncConfig
mesh = make_mesh((2,2,2), ('pod','data','model'))
cfg = SyncConfig(mode='sync', compress_int8=True)
def body(g, e):
    return pod_sync_grads(g, cfg, e)
# fully manual: collectives-only body; partial-manual trips old-XLA bugs
f = shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
              axis_names=frozenset(mesh.axis_names), check_vma=False)
g = {'w': jax.random.normal(jax.random.PRNGKey(0), (8, 16))}
e = {'w': jnp.zeros((8, 16))}
total_err = []
with set_mesh(mesh):
    acc = jnp.zeros((8, 16))
    for i in range(24):
        m, e = jax.jit(f)(g, e)
        acc = acc + m['w']
        total_err.append(float(jnp.max(jnp.abs(acc / (i + 1) - g['w']))))
# single exchange is within quantization error; the EF-dithered running
# mean converges well below it (residual carried, not lost)
assert total_err[0] < 0.05, total_err[0]
assert total_err[-1] < total_err[0] / 3, total_err[::6]
print('OK ef', total_err[0], total_err[-1])
""",
        devices=8,
    )
    assert "OK ef" in out
