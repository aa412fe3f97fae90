"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """Single pod: 16×16 = 256 chips ("data", "model"). Multi-pod adds a
    leading "pod" axis (2 pods = 512 chips): DP spans pod×data; TP stays
    pod-local so model collectives never cross the inter-pod DCI.

    ``shape`` overrides the chip grid: a 2-tuple builds ("data", "model"),
    a 3-tuple ("pod", "data", "model") — the same helper builds the 1×8
    virtual-device CPU test mesh (``--mesh 1x8`` under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and the
    production pod, so axis names never drift between the two."""
    if shape is not None:
        shape = tuple(int(s) for s in shape)
        if len(shape) not in (2, 3) or any(s < 1 for s in shape):
            raise ValueError(
                f"mesh shape must be 2 (data, model) or 3 (pod, data, model) "
                f"positive ints, got {shape!r}"
            )
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
