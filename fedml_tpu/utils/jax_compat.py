"""The narrow slice of the jax API the engine uses, in one place.

One installation runs this repo (jax 0.9 here and on the chip machine),
so these are thin pass-throughs to the current surface
(``jax.shard_map``, ``jax.lax.axis_size``, ``jax.lax.pcast``) kept only
to spare the call sites, plus two sharding-introspection helpers for the
program catalog.
"""
from __future__ import annotations

from typing import Any

import jax

Pytree = Any


def shard_map(f, mesh, in_specs, out_specs, check_vma=None, axis_names=None):
    """``jax.shard_map``; ``None`` keeps jax's default for either knob."""
    kwargs = {}
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    if axis_names is not None:
        kwargs["axis_names"] = frozenset(axis_names)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


axis_size = jax.lax.axis_size


def pcast_varying(tree: Pytree, axis_names) -> Pytree:
    """Cast replicated leaves to device-varying over ``axis_names``."""
    return jax.tree.map(
        lambda p: jax.lax.pcast(p, tuple(axis_names), to="varying"), tree
    )


def sharding_mesh_axes(sharding) -> dict:
    """``{axis_name: size}`` of a sharding's mesh, or ``{}``.

    Introspection for the program catalog's mesh/sharding records:
    ``NamedSharding`` exposes a mesh; anything else
    (``SingleDeviceSharding``, opaque GSPMD shardings) reports no axes.
    """
    mesh = getattr(sharding, "mesh", None)
    if mesh is None:
        return {}
    return {str(name): int(size) for name, size in dict(mesh.shape).items()}


def pspec_str(sharding) -> str:
    """A stable one-line spelling of a sharding's partition spec.

    ``NamedSharding`` → ``"P('dp', None)"``-style; shardings without a
    ``spec`` (fully replicated, single-device, opaque GSPMD) render via
    ``repr`` truncated — the catalog wants a human-auditable label, not
    a round-trippable object.
    """
    spec = getattr(sharding, "spec", None)
    if spec is not None:
        return f"P{tuple(spec)!r}"
    return repr(sharding)[:80]


__all__ = [
    "axis_size",
    "pcast_varying",
    "pspec_str",
    "shard_map",
    "sharding_mesh_axes",
]
