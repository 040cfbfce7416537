"""Logical-axis sharding: model code names axes "dp"/"mp", the mesh maps
them to physical axes.

The model and launch layers never mention physical mesh axes. They
constrain activations with logical names:

    h = shd.constrain(h, ("dp", "mp", None))

and a launcher builds its mesh with `make_mesh` and activates it once:

    rules = shd.set_mesh(shd.make_mesh((4,), ("data",)))

"dp" resolves to every data-parallel axis present (("pod", "data") on the
multi-pod mesh, ("data",) on a single pod), "mp" to the "model" axis. With
no active mesh every helper is a no-op / replicated, so single-device
tests and CPU smoke runs import the same model code unchanged.

Any axis that does not evenly divide a dimension is dropped from that
dimension's spec (replicated) rather than erroring — smoke configs have
tiny dims that rarely divide a production axis. Each such drop is logged
ONCE per (logical, size, dim) so a fleet run cannot silently lose its
sharding; pass ``strict=True`` to `spec` to raise instead (the fleet
evaluation plane does, via ``lane_sharding(..., strict=True)``).
"""
from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_LOG = logging.getLogger(__name__)
_WARNED: set[tuple] = set()      # (logical, axis_size, dim) already logged

_DATA_AXES = ("pod", "data")   # outer-to-inner data-parallel axes
_MODEL_AXIS = "model"

Logical = Optional[str]        # "dp" | "mp" | physical axis name | None


@dataclasses.dataclass(frozen=True)
class MeshRules:
    """Resolved logical->physical axis mapping for one mesh."""

    mesh: Mesh
    dp: tuple[str, ...]        # physical data axes present in the mesh
    mp: str | None             # physical model axis, if present

    def resolve(self, logical: Logical):
        """Logical name -> PartitionSpec entry (axis name, tuple, or None)."""
        if logical is None:
            return None
        if logical == "dp":
            if not self.dp:
                return None
            return self.dp if len(self.dp) > 1 else self.dp[0]
        if logical == "mp":
            return self.mp
        return logical if logical in self.mesh.shape else None

    def axis_size(self, logical: Logical) -> int:
        if logical is None:
            return 1
        if logical == "dp":
            return math.prod(self.mesh.shape[a] for a in self.dp) \
                if self.dp else 1
        if logical == "mp":
            return self.mesh.shape[self.mp] if self.mp else 1
        return self.mesh.shape.get(logical, 1)

    def spec(self, logicals, shape, *, strict: bool = False) -> P:
        """Build a PartitionSpec, dropping axes that don't divide dims.

        A requested axis that doesn't evenly divide its dimension is
        replicated (and logged once per (logical, size, dim) triple);
        with ``strict=True`` it raises instead, so fleet-scale runs
        can't silently lose their sharding."""
        entries = []
        for i, dim in enumerate(shape):
            logical = logicals[i] if i < len(logicals) else None
            size = self.axis_size(logical)
            phys = self.resolve(logical)
            if phys is None or size <= 1:
                entries.append(None)
            elif dim % size != 0:
                if strict:
                    raise ValueError(
                        f"axis {logical!r} (size {size}) does not divide "
                        f"dim {i} of shape {tuple(shape)}; pad the dim or "
                        f"drop strict= to replicate")
                key = (logical, size, dim)
                if key not in _WARNED:
                    _WARNED.add(key)
                    _LOG.warning(
                        "sharding axis %r (size %d) does not divide dim %d"
                        " — replicating (logged once per shape)",
                        logical, size, dim)
                entries.append(None)
            else:
                entries.append(phys)
        return P(*entries)


_ACTIVE: MeshRules | None = None


def make_mesh(shape, axis_names, *, devices=None) -> Mesh:
    """The repository's one mesh constructor: `jax.make_mesh` with every
    axis Auto. `jax.make_mesh` builds Explicit axes by default, and
    `constrain` (`with_sharding_constraint`) accepts only Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def set_mesh(mesh: Mesh | None) -> MeshRules | None:
    """Activate `mesh` for all subsequent helpers; None deactivates."""
    global _ACTIVE
    if mesh is None:
        _ACTIVE = None
        return None
    names = mesh.axis_names
    _ACTIVE = MeshRules(
        mesh=mesh,
        dp=tuple(a for a in _DATA_AXES if a in names),
        mp=_MODEL_AXIS if _MODEL_AXIS in names else None)
    return _ACTIVE


def active() -> MeshRules | None:
    return _ACTIVE


def constrain(x: jax.Array, logicals) -> jax.Array:
    """with_sharding_constraint under the active mesh; identity without."""
    rules = _ACTIVE
    if rules is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, rules.spec(logicals, x.shape)))


def lane_sharding(shape, *, w_axis: int = 1,
                  strict: bool = False) -> NamedSharding | None:
    """NamedSharding for the simulator's fused lane arrays: the workload
    axis (`w_axis`, default 1 for [P, W, ...] batches; pass 0 for a bare
    [W] / [W, M] tensor, 2 for the matrix runner's [S, Z, W, M]) shards
    over "dp", everything else replicates. Returns None with no active
    mesh so callers can skip the device_put."""
    rules = _ACTIVE
    if rules is None:
        return None
    w_axis = w_axis % max(len(shape), 1)
    logicals = tuple("dp" if i == w_axis else None
                     for i in range(len(shape)))
    return NamedSharding(rules.mesh,
                         rules.spec(logicals, shape, strict=strict))


# ------------------------------------------------------- tree shardings ----
def _path_name(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def _fsdp_spec(rules: MeshRules, shape) -> P:
    """ZeRO-3 style: shard the largest dp-divisible dim, replicate rest."""
    dp_size = rules.axis_size("dp")
    best = None
    if dp_size > 1 and len(shape) >= 1:
        divisible = [i for i, d in enumerate(shape)
                     if d % dp_size == 0 and d >= dp_size]
        if divisible:
            best = max(divisible, key=lambda i: shape[i])
    entries = [rules.resolve("dp") if i == best else None
               for i in range(len(shape))]
    return P(*entries)


def param_shardings(tree: Any):
    """NamedSharding pytree for params (or same-structured trees like the
    optimizer's master/m/v). Expert weights shard E over "mp" and D over
    "dp" (matching the shard_map EP path in repro.models.moe); everything
    else is FSDP-sharded over "dp". Scalars and vectors replicate."""
    rules = _ACTIVE
    if rules is None:
        raise RuntimeError("param_shardings requires set_mesh(...) first")

    def one(path, leaf):
        name = _path_name(path)
        shape = leaf.shape
        if len(shape) <= 1:
            return NamedSharding(rules.mesh, P())
        if name.endswith(("w_gate", "w_up")) and len(shape) == 3:
            return NamedSharding(rules.mesh,
                                 rules.spec(("mp", "dp", None), shape))
        if name.endswith("w_down") and len(shape) == 3:
            return NamedSharding(rules.mesh,
                                 rules.spec(("mp", None, "dp"), shape))
        if name.endswith("router"):
            return NamedSharding(rules.mesh, P())
        return NamedSharding(rules.mesh, _fsdp_spec(rules, shape))

    return jax.tree_util.tree_map_with_path(one, tree)


def batch_shardings(tree: Any):
    """Shard the leading (batch) dim of every leaf over "dp"."""
    rules = _ACTIVE
    if rules is None:
        raise RuntimeError("batch_shardings requires set_mesh(...) first")

    def one(leaf):
        return NamedSharding(rules.mesh,
                             rules.spec(("dp",), leaf.shape))

    return jax.tree.map(one, tree)


def cache_shardings(cache: Any, cfg):
    """Decode-cache shardings: batch dim over "dp" (axis 1 for the
    lax.scan-stacked per-layer subtrees, axis 0 for the unstacked leading
    dense layers)."""
    rules = _ACTIVE
    if rules is None:
        raise RuntimeError("cache_shardings requires set_mesh(...) first")

    def one(path, leaf):
        name = _path_name(path)
        batch_axis = 0 if name.startswith("dense_layers") else 1
        if len(leaf.shape) <= batch_axis:
            return NamedSharding(rules.mesh, P())
        logicals = [None] * len(leaf.shape)
        logicals[batch_axis] = "dp"
        return NamedSharding(rules.mesh,
                             rules.spec(tuple(logicals), leaf.shape))

    return jax.tree_util.tree_map_with_path(one, cache)
