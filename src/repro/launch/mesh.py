"""Production mesh definitions.

Single pod: 16x16 = 256 chips, axes ('data', 'model').
Multi-pod:  2 x 16 x 16 = 512 chips, axes ('pod', 'data', 'model') — the
'pod' axis is the slow-fabric (DCN) boundary where the paper's k-step
merging applies; 'data'/'model' live on in-pod ICI.

Defined as functions (never module-level constants) so importing this
module touches no jax device state.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    """The one mesh constructor: every axis Auto.  ``jax.make_mesh`` now
    defaults to Explicit axes, whose sharded shard_map outputs cannot feed
    the plain ``jnp.take`` that follows the routed pull."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(n_pod: int = 2, data: int = 2, model: int = 2) -> jax.sharding.Mesh:
    """Small mesh over host (CPU) devices for distributed tests/benches."""
    return _make_mesh((n_pod, data, model), ("pod", "data", "model"))


# TPU v5e hardware constants (roofline targets).
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
