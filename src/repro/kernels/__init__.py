"""Pallas TPU kernels for the perf-critical hot spots, with pure-jnp oracles.
Kernels compile with Mosaic on a TPU and run interpreted elsewhere (see
ops.py); flash_attention.py's header has the tiling."""

from . import ops, ref  # noqa: F401
