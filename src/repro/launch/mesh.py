"""Device meshes for MoSKA runs.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; everything else
must see the real device count). Every mesh is built with Auto axes: the
repo's sharding code (``repro.sharding.lsc``, ``core.disagg``) places
arrays with sharding constraints and ``shard_map``, which JAX's Explicit
axes (the ``jax.make_mesh`` default) do not accept. Install a mesh with
``jax.set_mesh(mesh)`` so that ``lsc`` sees it.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Sequence[int], axes: Sequence[str]):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: Optional[int] = None):
    """(data, model) mesh over whatever devices exist: all on ``data``
    unless ``model_axis`` devices are given to ``model``."""
    n = jax.device_count()
    m = model_axis or 1
    return _auto_mesh((n // m, m), ("data", "model"))
