"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships three artifacts: the Pallas implementation (<name>.py:
a pl.pallas_call with explicit BlockSpec VMEM tiling, or for flash
attention JAX's splash kernel and its backward), the public wrapper
(ops.py, auto-selects interpret mode off-TPU), and the pure-jnp oracle
(ref.py) that tests assert against.
"""

from repro.kernels import ops, ref  # noqa: F401
