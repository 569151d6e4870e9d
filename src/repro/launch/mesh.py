"""Production meshes (TPU v5e-like pods).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state, so unit tests keep their single CPU device.

Hardware model used across roofline/benchmarks (per the brief):
  197 TFLOP/s bf16 per chip | 819 GB/s HBM | ~50 GB/s/link ICI
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per ICI link
DCN_BW = 25e9 / 8 * 4        # bytes/s per host NIC (cross-pod, 4x25G)
HBM_BYTES = 16 * 2**30       # per chip


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (reduced meshes for tests, elasticity experiments).
    Axes are ``Auto``: shardings come from the logical-axis rules and
    ``with_sharding_constraint``, not from the types of the arrays."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
