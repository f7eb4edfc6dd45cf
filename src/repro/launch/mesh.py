"""Production mesh construction (multi-pod dry-run spec).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state. Single-pod:
(data=16, model=16) = 256 chips; multi-pod: (pod=2, data=16, model=16) =
512 chips. The 'pod' axis extends data parallelism across ICI-disconnected
pods (DCN): gradient all-reduce crosses pods once per step, FSDP gathers
stay pod-local (DESIGN.md §5).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small (data, model) mesh over available host devices (tests, examples)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
