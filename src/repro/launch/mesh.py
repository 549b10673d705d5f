"""Mesh construction: the one place that decides the mesh's axis types.

Every mesh in this package (and its tests) is built here with *Auto* axes.
The sharding layer (``steps._make_constrain``, ``distributed.sharding``,
the cov ``shard_map`` in ``kernels.ops``) places GSPMD hints with
``with_sharding_constraint``, which only accepts Auto axes; recent JAX
defaults ``jax.make_mesh`` to Explicit axes, so a bare ``jax.make_mesh``
call breaks every hint.

FUNCTIONS (not module constants) so importing this module never touches
jax device state — jax locks the device count on first backend init, and
the dry-run must set XLA_FLAGS before that happens.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis Auto (GSPMD-propagated)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod'
    axis (data parallelism across the inter-pod DCN/ICI boundary)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Degenerate mesh over the actually-available devices (tests, examples)."""
    n = len(jax.devices())
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def serving_mesh(mesh=None):
    """Mesh a server runs under: the caller's, else a host mesh when there
    is more than one device, else None — one device needs no sharding."""
    if mesh is not None:
        return mesh
    return make_host_mesh() if len(jax.devices()) > 1 else None


def make_calib_mesh(dp: int = 0):
    """Data-only mesh for sharded stage-1 calibration collection
    (``CompressConfig.calib_mesh="auto"`` resolves here).

    Covariance accumulation is a sum over token rows, so calibration shards
    purely over data — no model axis.  ``dp`` caps the degree (0 = every
    available device)."""
    n = len(jax.devices())
    if dp:
        n = min(dp, n)
    return make_mesh((n,), ("data",))
