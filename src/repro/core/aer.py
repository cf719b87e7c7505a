"""Address Event Representation (AER) spike packing.

Paper: "we send 'axonal spike' messages that carry the identifiers of spiking
neurons and are packed in groups that have the same spike emission time and
the same target process".

SPMD adaptation (DESIGN.md §2): messages are fixed-capacity int32 buffers of
spiking gids, ascending, padded with INVALID; slot 0 of the companion lane is
the spike count (the paper's single-word counter phase rides inside the same
buffer instead of a separate rendezvous round-trip).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

INVALID = np.int32(2 ** 31 - 1)   # host constant: importing claims no device


def compact_indices(mask: jnp.ndarray, cap: int, fill: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sort-free compaction: ascending indices of True entries of `mask`,
    padded with `fill` to static length `cap`.  Rank = exclusive cumsum of
    the mask, so the scatter preserves index order — identical output to
    `jnp.sort(where(mask, iota, fill))[:cap]` at O(N) instead of
    O(N log N).  The single compaction primitive behind both the AER wire
    (`pack`) and the event backend's spike/source lists
    (`event_engine`).  Returns (ids[cap], n_dropped)."""
    n = mask.shape[0]
    rank = jnp.cumsum(mask) - 1                        # rank among selected
    idx = jnp.where(mask & (rank < cap), rank, cap)    # cap == oob -> drop
    ids = jnp.full((cap,), fill, jnp.int32).at[idx].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    dropped = jnp.maximum(0, mask.sum(dtype=jnp.int32) - cap)
    return ids, dropped


def pack(spiked: jnp.ndarray, gid: jnp.ndarray, capacity: int
         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(spiked[N] bool, gid[N]) -> (ids[capacity] ascending, count).

    Padding entries are INVALID (at the tail).  capacity >= N always holds
    when capacity == N (every neuron can spike at most once per step, the
    refractory reset guarantees it).

    The per-shard gid table is ascending by local index for every
    placement (`topology.owned_gids` sorts), so the order-preserving
    `compact_indices` keeps the ascending order `match_sources`'
    searchsorted needs.
    """
    n = gid.shape[0]
    sel = spiked & (gid >= 0)
    idx, dropped = compact_indices(sel, capacity, fill=n)
    ids = jnp.where(idx < n, gid[jnp.minimum(idx, n - 1)].astype(jnp.int32),
                    INVALID)
    count = sel.sum(dtype=jnp.int32) - dropped
    return ids, count


def match_sources(ids: jnp.ndarray, src_gid: jnp.ndarray) -> jnp.ndarray:
    """Mark which local sources appear in a received AER buffer.

    ids: [C] ascending spike gids (INVALID padded);
    src_gid: [S] ascending local source table (-1 padded at *front* is not
    allowed; -1 pads are at arbitrary positions masked by >= 0).
    Returns [S] bool.
    """
    pos = jnp.searchsorted(ids, src_gid.astype(jnp.int32))
    pos = jnp.clip(pos, 0, ids.shape[0] - 1)
    hit = ids[pos] == src_gid
    return hit & (src_gid >= 0)
