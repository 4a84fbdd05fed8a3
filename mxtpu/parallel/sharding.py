"""Sharding rules: name-pattern → PartitionSpec, the TPU-native analogue
of the reference's per-parameter KVStore key placement
(``src/kvstore/kvstore_dist.h`` key sharding [path cite]).

The reference sharded parameter-server keys by range over server nodes;
here a rule table maps parameter names (regex) to ``PartitionSpec`` over
the logical mesh axes, and XLA materializes the layout. This is the t5x/
maxtext "logical axis rules" pattern, kept deliberately small.
"""
from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["P", "ShardingRules", "named", "shard_pytree", "constrain",
           "mcon", "replicated", "batch_spec", "key_str",
           "global_device_put", "bytes_per_device"]


def global_device_put(arr, sharding: "NamedSharding"):
    """device_put that also works onto a multi-process (not fully
    addressable) mesh: global placement accepts HOST arrays, so a
    committed device array takes a host hop first — correct under
    SPMD, where every process holds the same values. An array that is
    itself global already carrying the target sharding passes through;
    re-placing a global array onto a DIFFERENT sharding has no
    process-local path and raises with the fix."""
    if sharding.is_fully_addressable:
        return jax.device_put(arr, sharding)
    if isinstance(arr, jax.Array) and not arr.is_fully_addressable:
        if arr.sharding == sharding:
            return arr
        raise ValueError(
            "cannot re-place a global (non-addressable) array onto a "
            f"different sharding ({arr.sharding} -> {sharding}); "
            "rebuild it from host values on every process instead")
    import numpy as _np
    return jax.device_put(_np.asarray(arr), sharding)


def named(mesh: Mesh, *spec) -> NamedSharding:
    """``named(mesh, 'dp', None)`` → NamedSharding(mesh, P('dp', None))."""
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_spec(mesh: Optional[Mesh] = None) -> P:
    """Canonical batch sharding: leading dim over the data axes
    (dp, fsdp) — filtered to the axes ``mesh`` actually has, so custom
    meshes (e.g. ``('data','model')``) don't crash; with none of the
    canonical axes present the batch replicates and the caller should
    shard explicitly."""
    axes = ("dp", "fsdp")
    if mesh is not None:
        axes = tuple(a for a in axes if a in mesh.axis_names)
    return P(axes) if axes else P()


class ShardingRules:
    """Ordered (regex → PartitionSpec) table.

    >>> rules = ShardingRules([
    ...     (r".*attn.*(wq|wk|wv)$", P("fsdp", "tp")),
    ...     (r".*w_embed$",          P("tp", "fsdp")),
    ...     (r".*",                  P()),
    ... ])
    >>> rules.spec("layer3_attn_wq")   # first match wins
    """

    def __init__(self, rules: Sequence[Tuple[str, P]]):
        self._rules = [(re.compile(pat), spec) for pat, spec in rules]

    def spec(self, name: str) -> P:
        for pat, spec in self._rules:
            if pat.search(name):
                return spec
        return P()

    def sharding(self, mesh: Mesh, name: str) -> NamedSharding:
        return NamedSharding(mesh, self.spec(name))

    def tree_specs(self, tree: Any, prefix: str = "") -> Any:
        """Map a pytree of arrays to a matching pytree of PartitionSpecs,
        using '/'-joined key paths as names."""
        paths_and_leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = []
        for path, _leaf in paths_and_leaves:
            name = prefix + "/".join(_key_str(k) for k in path)
            specs.append(self.spec(name))
        return jax.tree_util.tree_unflatten(treedef, specs)


def key_str(k) -> str:
    """Canonical string for one pytree path entry (shared by every
    name-keyed pytree walk in mxtpu — keep this the single source)."""
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    if hasattr(k, "name"):
        return str(k.name)
    return str(k)


_key_str = key_str  # internal alias


def shard_pytree(tree: Any, mesh: Mesh, rules: "ShardingRules",
                 prefix: str = "") -> Any:
    """device_put every leaf with its rule-derived NamedSharding — the
    rebuild's ``kv.init`` (replicate/shard params onto the mesh)."""
    specs = rules.tree_specs(tree, prefix)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)


def bytes_per_device(tree: Any, specs: Any, mesh: Optional[Mesh]) -> int:
    """Bytes one device of ``mesh`` holds of ``tree`` laid out by
    ``specs`` (a matching tree of ``PartitionSpec`` or
    ``NamedSharding``): each leaf's bytes over the sizes of the axes its
    spec names. Shapes alone, so the leaves may be abstract."""
    def split(spec) -> int:
        axes = [a for e in getattr(spec, "spec", spec) if e
                for a in ((e,) if isinstance(e, str) else e)]
        return math.prod(mesh.shape.get(a, 1) for a in axes) if mesh else 1
    flat = jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, (P, NamedSharding)))
    return sum(math.prod(x.shape) * x.dtype.itemsize // split(s)
               for x, s in zip(jax.tree.leaves(tree), flat))


def _filter_spec(spec, axis_names) -> P:
    """Drop axes the mesh doesn't have (model code names the full
    dp/fsdp/sp/tp layout; smaller meshes ignore the missing axes)."""
    names = set(axis_names)

    def keep(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in names)
            return kept if kept else None
        return e if e in names else None

    return P(*[keep(e) for e in spec])


def mcon(mesh: Optional[Mesh], x, *spec):
    """Sharding constraint against an EXPLICIT mesh (the serving/MoE
    paths, where there is no ambient ``use_mesh`` inside a caller's
    jit); falls back to the ambient-mesh :func:`constrain` when mesh
    is None. Unknown axes are filtered, so call sites name the full
    canonical layout and smaller meshes ignore what they lack."""
    if mesh is None:
        return constrain(x, *spec)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, _filter_spec(P(*spec), mesh.axis_names)))


def constrain(x, *spec):
    """``with_sharding_constraint`` against the ambient mesh (mxtpu
    ``use_mesh`` or jax's own mesh context). Explicit no-op when no mesh
    is ambient; with a mesh present, spec errors (bad rank, unknown
    axis style) propagate instead of being swallowed."""
    from .mesh import current_mesh
    mesh = current_mesh()
    if mesh is not None:
        pspec = _filter_spec(spec, mesh.axis_names)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, pspec))
    am = jax.sharding.get_abstract_mesh()
    # inside shard_map, axes are Manual and constraints may only name
    # the remaining Auto axes (e.g. model code running under a gpipe
    # stage): constrain over those; no ambient mesh, or a fully manual
    # one, makes this a no-op
    auto = tuple(a for a, t in zip(am.axis_names, am.axis_types)
                 if t == jax.sharding.AxisType.Auto)
    if not auto:
        return x
    return jax.lax.with_sharding_constraint(
        x, _filter_spec(spec, auto))
