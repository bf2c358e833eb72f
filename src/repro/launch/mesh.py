"""Production mesh definitions (TPU v5e target).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run forces 512 host devices; tests and
benches must keep seeing 1).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes the compiler partitions (GSPMD ``Auto``): the
    models place arrays with ``with_sharding_constraint`` and leave the
    rest to propagation.  ``jax.make_mesh`` defaults to ``Explicit``
    axes, under which sharded gathers (an embedding lookup into a
    vocab-sharded table) must name their output sharding and fail."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh for tests/examples on CPU."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_serving_mesh(n_devices=None):
    """Serving mesh: every local device on the tensor-parallel "model"
    axis (a trivial "data" axis keeps the logical-axis maps and preset
    rules shared with training).  ``ContinuousEngine(mesh=...)`` shards
    attention heads, the paged KV pool and MoE experts over it; on CPU
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` forces a
    4-device host platform, which is how the sharded serving tests and
    bench lane run without accelerators."""
    if n_devices is None:
        n_devices = len(jax.devices())
    return _auto_mesh((1, n_devices), ("data", "model"))


# TPU v5e hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
