"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The single-pod mesh is
16x16 = 256 chips (v5e pod); multi-pod adds a leading "pod" axis (2 pods =
512 chips).  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to build these meshes on CPU.
"""

from __future__ import annotations

import jax


def _auto(n: int) -> tuple:
    # the sharding rules place arrays with with_sharding_constraint, which
    # needs Auto axes (jax.make_mesh defaults to Explicit ones)
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_test_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CI-scale sharding tests (8 host devices)."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=_auto(2))


# TPU v5e hardware constants (per chip) — used by the roofline analysis.
HW = {
    "peak_bf16_flops": 197e12,        # FLOP/s
    "hbm_bandwidth": 819e9,           # B/s
    "ici_link_bandwidth": 50e9,       # B/s per link
    "hbm_bytes": 16 * 2 ** 30,        # 16 GB
}


__all__ = ["make_production_mesh", "make_test_mesh", "HW"]
