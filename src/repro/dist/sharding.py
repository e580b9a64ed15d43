"""Logical-axis sharding: Runtime + the logical -> mesh-axis mapping.

Array specs name their dims with *logical* axes. This module owns the single
mapping from those names to physical mesh axes:

  tensor-parallel ('model') : vocab, heads, ff, experts, inner, cache_seq
  data-parallel / FSDP      : embed, batch  -> ('pod', 'data') — whichever of
                              the two exist on the mesh, in that order
  replicated                : everything else (kv, head, eff, state, ...)

Two fallbacks keep every mesh valid instead of erroring:
  * missing axis — a rule that names a mesh axis the mesh doesn't have
    replicates that dim (lets the same specs drive 1-device tests and
    multi-chip meshes);
  * divisibility — a dim that doesn't divide by its axis size replicates
    (e.g. 40 heads on a 16-wide 'model' axis). Callers can collect these
    via the `fallbacks` list.

`Runtime` is a frozen dataclass so variants derive via
`dataclasses.replace`. `ShardedUHNSW.shard_over` reads only its `mesh` and
`dp_axes` to place the stacked segment axis; `logical_to_spec` has no
caller in the package (its rules are pinned by tests/test_dist.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any

import jax
from jax.sharding import PartitionSpec as P

# logical axes that shard over the tensor-parallel ('model') axis
_TP_AXES = frozenset({"vocab", "heads", "ff", "experts", "inner", "cache_seq"})
# logical axes that shard over the data-parallel / FSDP axes
_DP_AXES = frozenset({"embed", "batch"})


@dataclass(frozen=True)
class Runtime:
    """A mesh and its data-parallel axes.

    rules: per-logical-axis overrides (axis name, axis tuple, or None to
    replicate) consulted before the built-in mapping.
    """

    mesh: Any
    rules: dict = field(default_factory=dict)
    full_dp: bool = False          # ZeRO-3 over *all* mesh axes, no TP

    @property
    def dp_axes(self) -> tuple[str, ...]:
        if self.full_dp:
            return tuple(self.mesh.axis_names)
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)

    @property
    def tp_axis(self) -> str:
        return "model"

    @property
    def dp_size(self) -> int:
        return int(math.prod(self.mesh.shape[a] for a in self.dp_axes))

    @property
    def tp_size(self) -> int:
        if self.full_dp or "model" not in self.mesh.axis_names:
            return 1
        return int(self.mesh.shape["model"])


def _resolve(name: str | None, rt: Runtime):
    """Logical axis name -> mesh axis name / axis tuple / None (replicate)."""
    if name is None:
        return None
    if name in rt.rules:
        return rt.rules[name]
    if name in _DP_AXES:
        dp = rt.dp_axes
        if not dp:
            return None
        return dp if len(dp) > 1 else dp[0]
    if name in _TP_AXES:
        return None if rt.full_dp else rt.tp_axis
    return None


def logical_to_spec(
    logical: tuple[str | None, ...],
    shape: tuple[int, ...],
    rt: Runtime,
    fallbacks: list | None = None,
) -> P:
    """Map logical dim names to a PartitionSpec, with safety fallbacks.

    A dim replicates (None entry) when its rule names a mesh axis that does
    not exist, or when the dim size is not divisible by the axis size; the
    latter is recorded in `fallbacks` as (logical_name, dim, axis_size).
    """
    assert len(logical) == len(shape), (logical, shape)
    names = set(rt.mesh.axis_names)
    entries = []
    for name, dim in zip(logical, shape):
        ax = _resolve(name, rt)
        if ax is None:
            entries.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        if any(a not in names for a in axes):
            entries.append(None)
            continue
        size = int(math.prod(rt.mesh.shape[a] for a in axes))
        if size > 1 and dim % size != 0:
            if fallbacks is not None:
                fallbacks.append((name, dim, size))
            entries.append(None)
            continue
        entries.append(ax)
    return P(*entries)


def abstract_mesh(axis_sizes: tuple[int, ...], axis_names: tuple[str, ...]):
    """`jax.sharding.AbstractMesh` from parallel size / name tuples."""
    return jax.sharding.AbstractMesh(tuple(axis_sizes), tuple(axis_names))
