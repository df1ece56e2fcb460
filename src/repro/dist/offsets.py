"""Exclusive prefix-sum offsets for parallel writers (MPI_Exscan analogue).

The paper's cluster layer computes each rank's byte offset into the shared
per-quantity output file as an exclusive scan over the compressed buffer
sizes.  ``exclusive_offsets_np`` is the single-process reference;
``exclusive_offsets_sharded`` runs the same collective under ``shard_map``
(per-shard local cumsum + all-gathered base from preceding shards), which is
exactly the two-phase Exscan a multi-host fleet would execute.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["exclusive_offsets_np", "exclusive_offsets_sharded"]


def exclusive_offsets_np(sizes) -> np.ndarray:
    """offsets[i] = sum(sizes[:i]); offsets[0] = 0."""
    s = np.asarray(sizes, np.int64)
    out = np.zeros_like(s)
    if s.size > 1:
        np.cumsum(s[:-1], out=out[1:])
    return out


def exclusive_offsets_sharded(sizes, mesh, axis_name: str):
    """Exclusive scan of ``sizes`` sharded along ``axis_name`` of ``mesh``.

    Each shard computes its local exclusive cumsum and adds the total of all
    preceding shards (one all-gather of per-shard totals — O(devices) bytes).
    """
    from jax.sharding import PartitionSpec as P

    def _exscan(local):
        totals = jax.lax.all_gather(jnp.sum(local), axis_name)
        idx = jax.lax.axis_index(axis_name)
        base = jnp.sum(jnp.where(jnp.arange(totals.shape[0]) < idx, totals, 0))
        return jnp.cumsum(local) - local + base

    fn = jax.shard_map(_exscan, mesh=mesh,
                       in_specs=P(axis_name), out_specs=P(axis_name))
    return fn(jnp.asarray(sizes))
