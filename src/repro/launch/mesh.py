"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e-256-class).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis is
data-parallel by default (gradient all-reduce crosses the pod boundary) and
can optionally host a 2-stage pipeline (ArchConfig.pipeline_stages=2).

Defined as functions so importing this module never touches jax device state
(the dry-run sets XLA_FLAGS before any jax import; smoke tests see 1 device).

Every axis is ``AxisType.Auto``: GSPMD propagates shardings from the
``NamedSharding`` placements and the ``hint`` constraints (parallel/hints.py).
``jax.make_mesh`` would otherwise default to ``Explicit`` axes, under which
untyped ops on sharded operands (the embedding gather) refuse to trace.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Mesh over the first ``data * model`` devices of this host."""
    return _auto_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
