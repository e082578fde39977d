"""Sharding API in one place: mesh context, axis types, shard_map.

- :data:`AxisType` — ``jax.sharding.AxisType``;
- :func:`make_mesh` — ``jax.make_mesh`` with all-Auto axis types by
  default;
- :func:`use_mesh` — ``jax.set_mesh`` (``None`` -> no-op context);
- :func:`shard_map` — ``jax.shard_map`` with partial-manual axes.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

import jax

AxisType = jax.sharding.AxisType


def auto_axis_types(n: int) -> Tuple[Any, ...]:
    """``(AxisType.Auto,) * n`` — the only axis-type tuple this repo uses."""
    return (AxisType.Auto,) * n


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types: Optional[Sequence[Any]] = None,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh``; ``axis_types=None`` means all-Auto."""
    types = (tuple(axis_types) if axis_types is not None
             else auto_axis_types(len(axis_names)))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=types, devices=devices)


def use_mesh(mesh: Optional[jax.sharding.Mesh]):
    """Context manager activating ``mesh`` (``None`` -> no-op context), so
    bare-``PartitionSpec`` sharding constraints resolve against it during
    tracing."""
    if mesh is None:
        return contextlib.nullcontext()
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh: jax.sharding.Mesh, in_specs, out_specs,
              axis_names: Optional[Iterable[str]] = None,
              check: bool = False):
    """``jax.shard_map`` with partial-manual axes.

    ``axis_names`` lists the axes ``f`` is manual over (all axes when
    ``None``); the rest stay automatically sharded.  ``check`` maps to
    ``check_vma``.
    """
    kwargs: Dict[str, Any] = {"check_vma": check}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
