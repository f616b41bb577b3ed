"""Production mesh construction (single pod 16x16 = 256 chips; multi-pod
2x16x16 = 512). Defined as functions so importing this module never touches
jax device state."""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # GSPMD-style meshes: sharding follows constraints, not array types
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 4):
    """Small mesh over whatever host devices exist (tests/benchmarks)."""
    n = len(jax.devices())
    model = min(model, n)
    return _auto_mesh((n // model, model), ("data", "model"))


# TPU v5e-class hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
