"""IVF index (inverted-file) — the paper's primary front stage.

Build: k-means coarse centroids (nlist), assign every record to its nearest
centroid, materialize fixed-capacity inverted lists (padded with -1 so the
whole search is jit-able / shard_map-able; padding follows the FAISS
convention of bounded list length).

Search: rank lists by centroid distance, take nprobe, gather member ids →
the candidate set handed to PQ-ADC scoring.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.quant.kmeans import assign, assign_rows, kmeans


@partial(jax.tree_util.register_dataclass,
         data_fields=("centroids", "lists", "list_len"), meta_fields=())
@dataclass(frozen=True)
class IVFIndex:
    centroids: jax.Array   # (nlist, D)
    lists: jax.Array       # (nlist, cap) int32, -1 padded
    list_len: jax.Array    # (nlist,) int32

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def cap(self) -> int:
        return self.lists.shape[1]


def fill_lists(ids: np.ndarray, nlist: int, cap: int
               ) -> tuple[np.ndarray, np.ndarray, int]:
    """Vectorized inverted-list fill: bucketize ``ids`` (N,) into a
    (nlist, cap') id matrix (-1 padded) + per-list lengths.

    No record is ever dropped: when the largest bucket exceeds ``cap`` the
    capacity SPILLS to fit it (returned ``n_spilled`` counts the rows past
    the requested cap, for skew monitoring).  Member order within each list
    matches the original append order (ascending record id) via a stable
    argsort, so the fill is a drop-in for the old O(N)-Python loop — minus
    its silent overflow drop.  Shared by the offline ``build`` and the
    streaming subsystem's ``compact()`` (anns/streaming.py).
    """
    n = ids.shape[0]
    counts = np.bincount(ids, minlength=nlist).astype(np.int32)
    n_spilled = int(np.maximum(counts - cap, 0).sum())
    cap = max(cap, int(counts.max()) if n else 1, 1)
    order = np.argsort(ids, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(n) - starts[ids[order]]
    lists = np.full((nlist, cap), -1, np.int32)
    lists[ids[order], pos] = order
    return lists, counts, n_spilled


def build(key: jax.Array, x: jax.Array, nlist: int, *, iters: int = 20,
          cap_factor: float = 3.0) -> IVFIndex:
    """Train centroids and fill inverted lists (host-side fill, device arrays
    out).  cap = cap_factor × N/nlist bounds skew; a hotter list spills the
    capacity rather than silently dropping members (the pre-vectorization
    fill loop lost any record past cap).  Assignment runs in row chunks,
    so no (N, nlist) temporary is built."""
    n = x.shape[0]
    centroids = kmeans(key, x, nlist, iters)
    ids = np.asarray(assign_rows(x, centroids))
    cap = int(cap_factor * n / nlist) + 1
    lists, lens, _ = fill_lists(ids, nlist, cap)
    return IVFIndex(centroids=jnp.asarray(centroids),
                    lists=jnp.asarray(lists), list_len=jnp.asarray(lens))


@partial(jax.jit, static_argnames=("nprobe",))
def probe(index: IVFIndex, q: jax.Array, *, nprobe: int) -> jax.Array:
    """Candidate ids for query q (D,) → (nprobe·cap,) int32 with -1 pads."""
    d = jnp.sum((index.centroids - q[None]) ** 2, axis=-1)
    _, top_lists = jax.lax.top_k(-d, nprobe)
    return index.lists[top_lists].reshape(-1)


def probe_batch(index: IVFIndex, qs: jax.Array, *, nprobe: int) -> jax.Array:
    return jax.vmap(lambda q: probe(index, q, nprobe=nprobe))(qs)


def assign_lists(index: IVFIndex, x: jax.Array) -> jax.Array:
    """Which inverted list each vector belongs to (nearest centroid)."""
    return assign(x, index.centroids)
