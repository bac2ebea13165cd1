"""Mesh-agnostic sharding hints for model internals.

Model code must run identically (a) unsharded on one CPU device (smoke
tests, examples), (b) under jit with the production mesh ambient
(dry-run / real training). ``constrain`` applies
``with_sharding_constraint`` only when a named mesh is ambient and only
with axis names that exist on it; otherwise it is an exact no-op.

Under vmap (the ASGD worker axis), the spec is automatically padded with a
leading None for the batched dimension by jax's batching rule.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _ambient_axis_names():
    return tuple(jax.sharding.get_abstract_mesh().axis_names)


def _axis_ok(a, names):
    if a is None:
        return True
    if isinstance(a, (tuple, list)):
        return all(b in names for b in a)
    return a in names


def constrain(x, *spec):
    """with_sharding_constraint over the axes of ``spec`` that the ambient
    mesh has; a no-op without an ambient mesh."""
    names = _ambient_axis_names()
    clean = tuple(a if _axis_ok(a, names) else None for a in spec)
    if all(a is None for a in clean):
        return x
    return jax.lax.with_sharding_constraint(x, P(*clean))
