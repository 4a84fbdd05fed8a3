"""Sharded large-embedding lookup — the TPU-native rebuild of the
reference's row_sparse parameter-server path (``src/kvstore/
kvstore_dist.h`` sparse push/pull + ``example/sparse/`` [path cites —
unverified], SURVEY.md §2.4 "Sparse/large-embedding parallel").

Where the reference kept huge embeddings sharded across PS servers and
workers pulled only the rows a batch touches, here the table is sharded
over a mesh axis (rows blocked over devices) and the lookup runs inside
``shard_map``: each device gathers the requested rows it owns locally
and a single ``psum`` assembles the result — XLA lays the collective on
ICI. The full table never materializes on one device, and the backward
pass is the exact transpose (local scatter-add of the incoming
gradient, no collective needed for the table grad).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["shard_embedding", "sharded_embedding_lookup"]


def shard_embedding(table, mesh: Mesh, axis: str = "fsdp"):
    """Place a (vocab, dim) table row-sharded over ``axis``. Vocab must
    divide by the axis size (pad the table if not — the reference's
    big-array key slicing had the same constraint per shard)."""
    return jax.device_put(table, NamedSharding(mesh, P(axis, None)))


def sharded_embedding_lookup(table, ids, mesh: Mesh,
                             axis: str = "fsdp"):
    """Differentiable lookup into a row-sharded table.

    ``table``: (V, D) sharded ``P(axis, None)``; ``ids``: int array,
    replicated. Returns ``(*ids.shape, D)`` replicated. Each device
    contributes only rows it owns; one psum over ``axis`` assembles
    them (rows are owned by exactly one shard, so the sum IS the
    gather).
    """
    if axis not in mesh.axis_names:
        # match the sharded path's out-of-range semantics (zeros), not
        # gather's default clamp — same inputs, same numerics
        valid = (ids >= 0) & (ids < table.shape[0])
        vals = table[jnp.clip(ids, 0, table.shape[0] - 1)]
        return jnp.where(valid[..., None], vals, 0)

    # every OTHER mesh axis is irrelevant to the table: keep the ids
    # and output replicated over them
    def local(tbl_shard, ids_rep):
        idx = jax.lax.axis_index(axis)
        vshard = tbl_shard.shape[0]
        lo = idx * vshard
        local_ids = jnp.clip(ids_rep - lo, 0, vshard - 1)
        vals = tbl_shard[local_ids]
        mine = ((ids_rep >= lo) & (ids_rep < lo + vshard))
        vals = jnp.where(mine[..., None], vals, 0).astype(tbl_shard.dtype)
        return jax.lax.psum(vals, axis)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(axis, None), P()), out_specs=P(),
        check_vma=False)(table, ids)
