"""Production mesh factory (the SAKURAONE 2-pod layout, TPU-adapted).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    """jax.make_mesh with every axis Auto (GSPMD-propagated shardings)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips).

    Axis order mirrors the paper's bandwidth hierarchy: "pod" is the thin
    cross-pod (DCN/spine) layer, "data"/"model" the fat in-pod layer, with
    "model" innermost on the highest-bandwidth links.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever this host has (smoke tests / examples): (1, N) data×model."""
    n = len(jax.devices())
    return _make_mesh((n, 1), ("data", "model"))
