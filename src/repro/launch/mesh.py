"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — smoke tests see 1 CPU device; only dryrun.py
forces 512 host devices before its first jax import.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(axes: tuple[str, ...]) -> tuple[AxisType, ...]:
    # the stack leaves layouts to sharding propagation (Auto axes);
    # jax.make_mesh defaults to Explicit axes
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) single-pod or (2,16,16) two-pod production mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests / examples / elastic rescale)."""
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def data_shards(mesh) -> int:
    out = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            out *= mesh.shape[a]
    return out
