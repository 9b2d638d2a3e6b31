"""Production mesh definitions.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis joins "data" for batch sharding, so gradient all-reduce crosses
the inter-pod links (DCI), proving the pod axis actually shards.

Defined as functions (not module constants) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n: int | None = None, name: str = "data"):
    """A 1-D mesh over whatever devices exist (tests / CPU smoke)."""
    n = n or jax.device_count()
    return jax.make_mesh((n,), (name,), axis_types=(AxisType.Auto,))


def dp_axes(multi_pod: bool) -> tuple:
    return ("pod", "data") if multi_pod else ("data",)


# TPU v5e hardware constants for the roofline terms
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
