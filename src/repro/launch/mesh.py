"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: GSPMD propagates shardings from the rule-table constraints
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
