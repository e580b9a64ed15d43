"""Logical-axis sharding: Runtime + the logical -> mesh-axis mapping.

Every parameter / activation / cache spec in the repo names its dims with
*logical* axes (see repro.models.params for the vocabulary). This module owns
the single mapping from those names to physical mesh axes:

  tensor-parallel ('model') : vocab, heads, ff, experts, inner, cache_seq
  data-parallel / FSDP      : embed, batch  -> ('pod', 'data') — whichever of
                              the two exist on the mesh, in that order
  replicated                : everything else (kv, head, eff, state, layers,
                              lora, seq_act unless rt.seq_shard, ...)

Two fallbacks keep every (arch x mesh) cell compilable instead of erroring:
  * missing axis — a rule that names a mesh axis the mesh doesn't have
    replicates that dim (lets the same specs drive 1-device tests and the
    512-chip dry-run);
  * divisibility — a dim that doesn't divide by its axis size replicates
    (e.g. qwen's 40 heads on a 16-wide 'model' axis). Callers can collect
    these via the `fallbacks` list to surface them in dry-run reports.

`Runtime` is a frozen dataclass so experiment variants derive via
`dataclasses.replace` (e.g. the weights-once path overrides rules['embed']).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# logical axes that shard over the tensor-parallel ('model') axis
_TP_AXES = frozenset({"vocab", "heads", "ff", "experts", "inner", "cache_seq"})
# logical axes that shard over the data-parallel / FSDP axes
_DP_AXES = frozenset({"embed", "batch"})


@dataclass(frozen=True)
class Runtime:
    """Mesh + parallelism mode flags, threaded through every model call.

    rules: per-logical-axis overrides (axis name, axis tuple, or None to
    replicate) consulted before the built-in mapping.
    """

    mesh: Any
    rules: dict = field(default_factory=dict)
    remat: bool = False
    explicit_tp: bool = False      # shard_map FFN matmuls instead of GSPMD
    seq_shard: bool = False        # shard activation seq dim over 'model'
    moe_decode_gather: bool = False  # weights-stationary decode MoE
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
    if name == "seq_act":
        return rt.tp_axis if rt.seq_shard and not rt.full_dp else None
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


def set_mesh(mesh):
    """Context manager activating `mesh` (`jax.sharding.set_mesh`)."""
    return jax.sharding.set_mesh(mesh)


def abstract_mesh(axis_sizes: tuple[int, ...], axis_names: tuple[str, ...]):
    """`jax.sharding.AbstractMesh` from parallel size / name tuples."""
    return jax.sharding.AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def constrain(x: jax.Array, rt: Runtime, logical: tuple[str | None, ...]):
    """with_sharding_constraint under the logical mapping (activation pin)."""
    spec = logical_to_spec(logical, x.shape, rt)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rt.mesh, spec))


def spec_shardings(specs, rt: Runtime):
    """ParamSpec tree -> NamedSharding tree (same structure as the params)."""
    from repro.models.params import _map_specs

    def mk(s):
        return NamedSharding(rt.mesh, logical_to_spec(s.logical, s.shape, rt))

    return _map_specs(mk, specs)


def param_struct(specs, rt: Runtime):
    """ParamSpec tree -> sharded ShapeDtypeStruct tree (dry-run contract)."""
    from repro.models.params import _map_specs

    def mk(s):
        sh = NamedSharding(rt.mesh, logical_to_spec(s.logical, s.shape, rt))
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)

    return _map_specs(mk, specs)
