"""Thin helpers over the installed JAX's mesh and compiled-program APIs.

* ``make_mesh`` — ``jax.make_mesh`` with every axis ``AxisType.Auto``
  unless the caller says otherwise (the GSPMD behaviour the executors'
  ``shard_map`` specs are written against).
* ``cost_analysis_dict`` — ``Compiled.cost_analysis()`` as a plain dict,
  empty on backends without a cost model.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, axis_types=None, devices=None):
    """``jax.make_mesh`` with all-``Auto`` axis types by default."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names), axis_types=axis_types, devices=devices
    )


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a flat dict (empty when the
    backend reports no cost model)."""
    return dict(compiled.cost_analysis() or {})
