"""Logical-axis sharding rules (FSDP + TP + EP + SP) for the model zoo.

Every tensor dimension is tagged with a logical name; ``spec()`` maps names
to mesh axes with a divisibility fallback (a dimension that does not divide
by its mesh axes is replicated — e.g. musicgen's 24 heads on a 16-wide
model axis).  The fallback warns once per (name, shape) — a silently
replicated dimension multiplies the per-device footprint by the mesh size,
which for serving-state rows would turn an 8-way shard into 8 full
replicas; layers that cannot afford that (the group-sharding layer) pass
``strict=True`` to make non-divisibility an error instead.  Rules:

  batch    -> ("pod", "data")     data parallel
  fsdp     -> ("pod", "data")     parameter/optimizer sharding (ZeRO-3)
  model    -> ("model",)          tensor parallel (Megatron column/row)
  heads/kv_heads/ff/vocab/experts -> ("model",)
  rows     -> ("pod", "data", "model")  serving-state point rows (the WLSH
              group states shard rows over every mesh axis, see
              distributed.group_sharding)
  seq      -> ()                  (("pod","data") for seq-sharded KV caches)
  layers/None -> replicated

``with_rules`` overrides rules locally (e.g. long-context decode shards the
KV-cache sequence over the data axes because batch == 1).
"""

from __future__ import annotations

import contextlib
import warnings
from typing import Iterable

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "spec",
    "shard",
    "shard_map_nocheck",
    "make_mesh",
    "named_sharding",
    "with_rules",
    "axis_size",
]


def shard_map_nocheck(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def make_mesh(shape: tuple[int, ...], names: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` makes Explicit axes by default, under which sharding
    constraints and the serving state's in-place row updates are refused;
    the program's meshes rely on the compiler propagating shardings.
    """
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(shape))


_DEFAULT_RULES: dict[str | None, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data"),
    "model": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "rows": ("pod", "data", "model"),
    "seq": (),
    "act_seq": ("model",),  # Megatron-SP residual stream between layers
    "kv_seq": (),
    "layers": (),
    None: (),
}

_rules_stack: list[dict] = [dict(_DEFAULT_RULES)]


def current_rules() -> dict:
    return _rules_stack[-1]


@contextlib.contextmanager
def with_rules(**overrides):
    new = dict(current_rules())
    for k, v in overrides.items():
        new[k] = tuple(v) if isinstance(v, (list, tuple)) else (v,)
    _rules_stack.append(new)
    try:
        yield
    finally:
        _rules_stack.pop()


def axis_size(mesh: Mesh, axes: Iterable[str]) -> int:
    s = 1
    for a in axes:
        if a in mesh.axis_names:
            s *= mesh.shape[a]
    return s


# (name, shape) pairs whose divisibility fallback already warned once —
# the fallback is deliberate for a handful of model-zoo dims (e.g. 24
# heads on a 16-wide model axis) and warning per call would be noise, but
# *silent* replication hides an N-fold footprint blowup from whoever
# sized the mesh.
_replication_warned: set[tuple] = set()


def spec(mesh: Mesh, names: tuple[str | None, ...],
         shape: tuple[int, ...] | None = None, *,
         strict: bool = False) -> P:
    """PartitionSpec from logical dim names, with divisibility fallback.

    A dimension whose size does not divide its mesh axes is replicated,
    with a once-per-(name, shape) ``UserWarning`` naming the footprint
    cost.  ``strict=True`` turns the fallback into a ``ValueError`` — the
    contract the group-sharding layer requests, where replicating the
    point rows would multiply the paging budget by the mesh size.
    """
    rules = current_rules()
    parts = []
    for i, name in enumerate(names):
        axes = tuple(a for a in rules.get(name, ()) if a in mesh.axis_names)
        if not axes:
            parts.append(None)
            continue
        if shape is not None:
            size = axis_size(mesh, axes)
            if shape[i] % size != 0:
                if strict:
                    raise ValueError(
                        f"dim {i} ({name!r}) of shape {tuple(shape)} does "
                        f"not divide mesh axes {axes} (size {size}); "
                        f"strict sharding refuses to replicate — pad the "
                        f"dimension to a multiple of {size}"
                    )
                key = (name, tuple(shape))
                if key not in _replication_warned:
                    _replication_warned.add(key)
                    warnings.warn(
                        f"replicating dim {i} ({name!r}) of shape "
                        f"{tuple(shape)}: size {shape[i]} does not divide "
                        f"mesh axes {axes} (size {size}) — every device "
                        f"holds a full copy ({size}x the sharded "
                        f"footprint)",
                        UserWarning,
                        stacklevel=2,
                    )
                # replicate instead of uneven-sharding stacked/scanned dims
                parts.append(None)
                continue
        parts.append(axes if len(axes) > 1 else axes[0])
    return P(*parts)


def named_sharding(mesh: Mesh, names, shape=None, *,
                   strict: bool = False) -> NamedSharding:
    return NamedSharding(mesh, spec(mesh, tuple(names), shape,
                                    strict=strict))


def shard(x, mesh: Mesh | None, *names):
    """with_sharding_constraint by logical names (no-op without mesh)."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, named_sharding(mesh, names, tuple(x.shape))
    )
