"""Sharding context: lets model code state *logical* layouts that only bind
when a mesh is active.

Models call ``constrain(x, "data", None, "model")``; under an active
``shard_ctx(mesh)`` this becomes ``jax.lax.with_sharding_constraint`` with the
named axes (pod+data are fused for the batch dimension on the multi-pod
mesh); with no context it is a no-op, so smoke tests and CPU examples run
unchanged. This is the single point where DP/TP/EP layouts are injected into
every architecture.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: F401

_state = threading.local()

# logical axis name -> tuple of mesh axes it maps to
_LOGICAL_DEFAULT = {
    "batch": ("pod", "data"),     # fused data-parallel axes
    "data": ("data",),
    "pod": ("pod",),
    "model": ("model",),
    "expert": ("model",),         # EP reuses the model axis
}


def _current():
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def shard_ctx(mesh: Mesh, logical_map: dict | None = None):
    """Activate sharding constraints for model code executed inside."""
    prev = _current()
    mapping = dict(_LOGICAL_DEFAULT)
    if logical_map:
        mapping.update(logical_map)
    # drop logical axes whose mesh axes are absent (single-pod mesh has no "pod")
    resolved: dict[str, tuple[str, ...]] = {}
    for name, axes in mapping.items():
        present = tuple(a for a in axes if a in mesh.axis_names)
        resolved[name] = present
    # fused groups (>1 configured mesh axes, e.g. batch = pod+data) keep the
    # tuple form in specs even when only one member axis is present
    fused = {name for name, axes in mapping.items() if len(axes) > 1}
    _state.ctx = (mesh, resolved, fused)
    try:
        yield
    finally:
        _state.ctx = prev


def resolve_spec(*logical: str | None) -> P:
    """Map logical axis names to a PartitionSpec under the active context."""
    ctx = _current()
    if ctx is None:
        return P(*logical)  # unused; constrain() no-ops without ctx
    _, mapping, fused = ctx
    parts = []
    for ax in logical:
        if ax is None:
            parts.append(None)
        else:
            mesh_axes = mapping.get(ax, ())
            if len(mesh_axes) == 0:
                parts.append(None)
            elif len(mesh_axes) == 1 and ax not in fused:
                parts.append(mesh_axes[0])
            else:
                parts.append(tuple(mesh_axes))
    return P(*parts)


def constrain(x: jax.Array, *logical: str | None) -> jax.Array:
    """with_sharding_constraint if a mesh context is active, else identity.

    Axes whose mesh extent does not divide the tensor dim are dropped to
    replicated (e.g. MQA's single KV head over a 16-way model axis) —
    avoiding GSPMD's 'involuntary full rematerialization' resharding path.
    """
    ctx = _current()
    if ctx is None:
        return x
    mesh = ctx[0]
    spec = resolve_spec(*logical)
    parts = list(spec) + [None] * (x.ndim - len(spec))
    for i, part in enumerate(parts):
        if part is None:
            continue
        axes = part if isinstance(part, tuple) else (part,)
        extent = 1
        for a in axes:
            extent *= mesh.shape[a]
        if x.shape[i] % extent != 0:
            parts[i] = None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


def active_mesh() -> Mesh | None:
    ctx = _current()
    return ctx[0] if ctx else None

