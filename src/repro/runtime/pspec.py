"""Logical-axis sharding rules (flax-linen-style, dependency-free).

Model code annotates activations with *logical* axis names via ``shard(x,
"batch", None, "embed")``. The runtime installs a rule set mapping logical
names to mesh axes (or None = replicate). When no rules are installed (pure
unit tests), ``shard`` is the identity — model code never imports mesh
details.

Rules used by this framework (DESIGN.md §5):

    batch   -> ("pod", "data") on the multi-pod mesh, ("data",) single-pod
    embed   -> None (activations keep full d_model)
    heads   -> "model" when the arch's head count divides the axis, else None
    kv_heads-> "model" or None likewise
    ffn     -> "model"
    vocab   -> "model"
    experts -> "model"
    fsdp    -> "data"  (parameter sharding only)
    seq     -> None (baseline) / "model" (sequence-sharded attention, §Perf)
    kv_seq  -> ("data", "model") for long-context decode cache
"""

from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "axis_rules", "current_rules", "logical_spec", "shard", "named_sharding",
    "AxisRules",
]

_state = threading.local()


class AxisRules:
    def __init__(self, mesh: Mesh | None, rules: dict[str, tuple[str, ...] | str | None]):
        self.mesh = mesh
        self.rules = dict(rules)

    def resolve(self, name: str | None):
        if name is None:
            return None
        if name not in self.rules:
            raise KeyError(f"no sharding rule for logical axis {name!r}")
        return self.rules[name]


def current_rules() -> AxisRules | None:
    return getattr(_state, "rules", None)


@contextmanager
def axis_rules(mesh: Mesh | None, rules: dict):
    prev = getattr(_state, "rules", None)
    _state.rules = AxisRules(mesh, rules) if mesh is not None else None
    try:
        yield
    finally:
        _state.rules = prev


def logical_spec(*names: str | None) -> PartitionSpec:
    r = current_rules()
    if r is None:
        return PartitionSpec()
    return PartitionSpec(*[r.resolve(n) for n in names])


def named_sharding(*names: str | None) -> NamedSharding | None:
    r = current_rules()
    if r is None or r.mesh is None:
        return None
    return NamedSharding(r.mesh, logical_spec(*names))


def shard(x: jax.Array, *names: str | None) -> jax.Array:
    """Apply a logical sharding constraint (identity when no rules active)."""
    s = named_sharding(*names)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)
