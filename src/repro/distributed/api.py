"""Mesh-agnostic sharding hints.

Model code calls ``constrain(x, "data", None, "model")`` to pin intermediate
activations; outside a mesh context (CPU unit tests, single device) this is
the identity, so the model zoo stays runnable anywhere.  Axis *names* given
here are logical; ``resolve_axis`` maps them onto whatever physical mesh axes
exist (the multi-pod mesh folds "pod" into "data" for activations).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

# logical -> physical axis mapping; "data" may expand to ("pod", "data").
_ACTIVE_RULES: Optional[dict] = None


def set_axis_rules(rules: Optional[dict]) -> None:
    """rules: {"data": ("pod", "data"), "model": ("model",)} or None to clear."""
    global _ACTIVE_RULES
    _ACTIVE_RULES = rules


def get_axis_rules() -> Optional[dict]:
    return _ACTIVE_RULES


def _active_mesh_axes() -> Optional[Tuple[Tuple[str, ...], Tuple[int, ...]]]:
    """(axis_names, axis_sizes) of the ambient mesh, or None when no mesh
    is active."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    return tuple(mesh.axis_names), tuple(mesh.axis_sizes)


def resolve(spec_names: Tuple[Optional[str], ...]) -> P:
    rules = _ACTIVE_RULES or {}
    out = []
    for name in spec_names:
        if name is None:
            out.append(None)
        else:
            phys = rules.get(name, ())
            if not phys:
                out.append(None)
            elif len(phys) == 1:
                out.append(phys[0])
            else:
                out.append(tuple(phys))
    return P(*out)


def mesh_axis_size(logical: str) -> int:
    """Active-mesh size of a logical axis ("data"/"model"); 1 if no mesh."""
    if _ACTIVE_RULES is None:
        return 1
    axes = _active_mesh_axes()
    if axes is None:
        return 1
    sizes = dict(zip(*axes))
    out = 1
    for phys in _ACTIVE_RULES.get(logical, ()):
        out *= sizes.get(phys, 1)
    return out


def constrain(x: jax.Array, *names: Optional[str]) -> jax.Array:
    """with_sharding_constraint if a mesh is active, else identity.

    Skips axes whose size does not divide the dim, and skips entirely on
    rank mismatch (helpers are reused at several ranks)."""
    if _ACTIVE_RULES is None:
        return x
    mesh_axes = _active_mesh_axes()
    if mesh_axes is None:
        return x
    if getattr(x, "ndim", None) != len(names):
        return x
    spec = resolve(names)
    # drop axis names the current mesh lacks or whose size doesn't divide
    axes = dict(zip(*mesh_axes))

    def keep(entry, dim):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in axes)
            if not kept:
                return None
            size = 1
            for a in kept:
                size *= axes[a]
            return kept if dim % size == 0 else None
        if entry not in axes or dim % axes[entry] != 0:
            return None
        return entry

    spec = P(*[keep(e, d) for e, d in zip(spec, x.shape)])
    return jax.lax.with_sharding_constraint(x, spec)
