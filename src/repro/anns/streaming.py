"""Streaming index subsystem: online inserts/deletes over a FaTRQ index.

The static pipeline (``build`` → ``SearchExecutor``) assumes an immutable
``(N, …)`` database.  A production RAG service ingests embeddings
continuously, so ``StreamingIndex`` makes the tiered layout MUTABLE without
a full rebuild (FreshDiskANN-style delta maintenance adapted to FaTRQ's
far-memory layout):

* **Row store** — every per-record array (PQ codes, TRQ levels + scalars,
  full vectors) lives in a capacity-padded device array; inserts append
  rows with ``lax.dynamic_update_slice`` (``trq.write_rows``), never
  touching existing rows, and the store doubles host-side when full.
  New rows are TRQ-encoded INCREMENTALLY (``trq.encode_rows``) against the
  frozen quantizers — per-record quantities are row-independent, so the
  appended codes are bit-identical to a full re-encode.

* **Delta lists** — per-IVF-list fixed-capacity spill pages of freshly
  inserted row ids, -1 padded so the datapath stays jit/shard_map-able.
  A full page grows by whole pages (shape change → one retrace).  The
  front stage probes base lists ∪ delta lists of the same top-``nprobe``
  centroids; delta candidates are counted separately (``delta_cand``) and
  their far-memory stream is billed to a DISTINCT ``delta:cxl`` ledger
  entry (``executor.fold_counts``).

* **Tombstones** — ``delete(gids)`` flips an alive bitmap; dead rows are
  masked out of the candidate set in the front stage (and therefore never
  reach refine/rerank).  Ids returned by ``search`` are stable GLOBAL ids
  (``row_gid``), monotonic across the index's lifetime.

* **Graph front** — ``search(front="graph")`` runs the CAGRA-style beam
  traversal over the mutable row store.  The adjacency is materialized
  lazily on first graph search and then maintained ONLINE
  (FreshDiskANN-style, ``index.graph``): ``insert`` wires each new row to
  its beam-search neighborhood (forward edges) and into its neighbors'
  reverse slots; ``delete`` leaves the graph alone — traversal routes
  THROUGH tombstoned rows, the front just masks them out of the candidate
  beam; ``compact()`` drops dead rows and patches edges through them with
  a one-hop contraction.  Rows appended since the last compaction count as
  ``delta_cand`` (their TRQ codes live in the delta region of far memory),
  so the graph front bills the same ``delta:cxl`` ledger entry the IVF
  base ∪ delta probe does.

* **Compaction / rebalancing** — when the drift metric crosses a
  threshold (tombstone fraction, delta fraction, or — once a shard
  assignment exists — the stale assignment's max shard load exceeding a
  fresh LPT partition's by more than the (4/3 − 1/3S) guarantee factor),
  ``compact()`` folds delta pages into freshly filled base lists
  (``ivf.fill_lists``), drops tombstones, and repacks the row store with
  one gather (``trq.gather_rows``); ``rebalance(shards)`` additionally
  re-partitions lists across shards with the same ``sharding.lpt_assign``
  greedy the static partitioner uses, reporting how many rows MOVED
  shards (moves are gathers of packed codes — TRQ codes are
  centroid-relative, so no row is ever re-encoded after insert).

Search equivalence: ``rebuild_static()`` assigns every surviving row from
scratch into fresh inverted lists (reusing the trained centroids/PQ/
calibration — retraining those on drifted data is a model update, not an
index-maintenance operation) and returns a plain ``FaTRQIndex`` + gid map.
``StreamingIndex.search`` matches its top-k exactly for both refine
backends — same probe set, same candidate SET (order differs, but every
pruning threshold is a kth-smallest over the same value multiset), same
survivors, same exact rerank — up to exact-f32 estimate ties at the
budget boundary (the same measure-zero caveat as ``anns.sharding``).
``search(shards=S)`` routes a snapshot through the sharded subsystem and
maps shard-local results back to global ids.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.anns import registry
from repro.anns.executor import SearchExecutor
from repro.anns.pipeline import FaTRQIndex, PipelineConfig
from repro.anns.sharding import lpt_assign
from repro.anns.stages import (Candidates, adc_score, fold_graph_front_cost,
                               fold_ivf_front_cost, rank_centroid_lists)
from repro.core import trq as trq_mod
from repro.index import graph as graph_mod
from repro.index import ivf as ivf_mod
from repro.memory import QueryCost
from repro.obs import metrics as obs_metrics, trace
from repro.quant import pq as pq_mod
from repro.quant.kmeans import assign


@dataclass(frozen=True)
class StreamingConfig:
    """Knobs of the mutable layer (the search knobs stay in
    ``PipelineConfig``)."""

    delta_page: int = 64           # slots per per-list delta spill page
    row_headroom: float = 0.25     # spare row capacity after grow/compact
    max_tombstone_frac: float = 0.3    # drift trigger: dead / (live+dead)
    max_delta_frac: float = 0.5        # drift trigger: delta rows / live
    auto_compact: bool = True      # fold automatically when drift trips


def _pad_rows(a: jax.Array, cap: int) -> jax.Array:
    """Zero-pad a per-record device array to ``cap`` leading rows."""
    pad = cap - a.shape[0]
    if pad <= 0:
        return a
    return jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], 0)


@partial(jax.jit, static_argnames=("nprobe",))
def _streaming_candidates(centroids, codebook, pq_codes, base_lists,
                          delta_lists, alive, queries, qvalid, *,
                          nprobe: int):
    """Generation-aware IVF front: probe base ∪ delta lists of the global
    top-``nprobe`` centroids, mask tombstones, ADC-score, and count delta
    candidates separately for the ledger."""
    _, top_lists = rank_centroid_lists(centroids, queries, nprobe=nprobe)
    nq = queries.shape[0]
    ids_b = base_lists[top_lists].reshape(nq, -1)
    ids_d = delta_lists[top_lists].reshape(nq, -1)
    ids = jnp.concatenate([ids_b, ids_d], axis=1)             # (Q, C)
    safe = jnp.maximum(ids, 0)
    valid = (ids >= 0) & alive[safe]                          # tombstone mask
    if qvalid is not None:                 # padded rows: no candidates
        valid = valid & qvalid[:, None]
    d0 = adc_score(codebook, pq_codes, safe, queries, valid)
    is_delta = jnp.broadcast_to(
        jnp.arange(ids.shape[1])[None, :] >= ids_b.shape[1], ids.shape)
    return (safe, valid, d0, is_delta, jnp.sum(valid),
            jnp.sum(valid & is_delta))


@dataclass
class StreamingFrontStage:
    """``FrontStage`` over a mutable generation: base ∪ delta probe with
    tombstone masking.  Implements the same protocol as ``IVFFrontStage``
    so the plain ``SearchExecutor`` runs the streaming datapath unchanged
    (its candidate ids are ROW ids — ``StreamingIndex.search`` maps the
    executor's output through ``row_gid``)."""

    centroids: jax.Array
    codebook: pq_mod.PQCodebook
    pq_codes: jax.Array
    base_lists: jax.Array
    delta_lists: jax.Array
    alive: jax.Array
    nprobe: int = 8
    name: str = "streaming"

    def candidates(self, queries: jax.Array,
                   qvalid: jax.Array | None = None) -> Candidates:
        safe, valid, d0, is_delta, n_cand, n_delta = _streaming_candidates(
            self.centroids, self.codebook, self.pq_codes, self.base_lists,
            self.delta_lists, self.alive, queries, qvalid,
            nprobe=self.nprobe)
        return Candidates(ids=safe, valid=valid, d0=d0,
                          counters={"front_cand": n_cand,
                                    "delta_cand": n_delta},
                          is_delta=is_delta)

    def fold_cost(self, cost: QueryCost, counts: dict[str, int],
                  layout) -> None:
        fold_ivf_front_cost(cost, counts, layout)


@partial(jax.jit, static_argnames=("iters", "beam", "expand", "n_base"))
def _graph_streaming_candidates(neighbors, x_score, codebook, pq_codes,
                                alive, queries, qvalid, *, iters: int,
                                beam: int, expand: int, n_base: int):
    """Tombstone-aware graph front: beam-search the maintained adjacency
    (which still routes THROUGH dead rows), mask tombstones out of the
    final beam, and count post-compaction rows as delta candidates."""
    gidx = graph_mod.GraphIndex(neighbors=neighbors)
    ids = jax.vmap(lambda q: graph_mod.search(gidx, x_score, q, iters=iters,
                                              beam=beam, expand=expand))(
        queries)                                              # (Q, beam)
    valid = alive[ids]
    if qvalid is not None:                 # padded rows: no candidates
        valid = valid & qvalid[:, None]
    d0 = adc_score(codebook, pq_codes, ids, queries, valid)
    is_delta = ids >= n_base
    return (ids, valid, d0, is_delta, jnp.sum(valid),
            jnp.sum(valid & is_delta))


@dataclass
class GraphStreamingFrontStage:
    """``FrontStage`` running the CAGRA-style traversal over a mutable
    generation: the online-maintained adjacency plus the alive bitmap.
    Post-compaction (no tombstones, no delta rows) its candidate stream is
    bit-identical to the static ``GraphFrontStage`` over ``rebuild_static``
    given the same adjacency — same beam search, same ADC scoring — which
    is exactly what the churn-equivalence pin tests."""

    graph: graph_mod.GraphIndex
    codebook: pq_mod.PQCodebook
    pq_codes: jax.Array        # (n_rows, M) — sliced to the live store
    alive: jax.Array           # (n_rows,) bool
    n_base: int                # rows ≥ n_base were inserted post-compact
    beam: int = 64
    iters: int = 32
    expand: int = 4
    name: str = "graph"
    x_score: jax.Array = None

    def __post_init__(self):
        if self.x_score is None:
            self.x_score = pq_mod.decode(self.codebook, self.pq_codes)

    def candidates(self, queries: jax.Array,
                   qvalid: jax.Array | None = None) -> Candidates:
        ids, valid, d0, is_delta, n_cand, n_delta = \
            _graph_streaming_candidates(
                self.graph.neighbors, self.x_score, self.codebook,
                self.pq_codes, self.alive, queries, qvalid,
                iters=self.iters, beam=self.beam, expand=self.expand,
                n_base=self.n_base)
        per_q = self.iters * self.expand * self.graph.degree
        nq = jnp.asarray(queries.shape[0], jnp.int32) if qvalid is None \
            else jnp.sum(qvalid).astype(jnp.int32)
        return Candidates(ids=ids, valid=valid, d0=d0,
                          counters={"front_cand": n_cand,
                                    "front_hops": nq * per_q,
                                    "delta_cand": n_delta},
                          is_delta=is_delta)

    def fold_cost(self, cost: QueryCost, counts: dict[str, int],
                  layout) -> None:
        fold_graph_front_cost(cost, counts, layout)


class StreamingIndex:
    """Mutable FaTRQ index: online inserts/deletes + drift-triggered
    compaction, searched through the existing refine backends.

    Host-side structures (inverted lists, delta pages, alive bitmap, gid
    maps) are numpy and mirrored to device lazily per generation; the
    heavy per-row payloads (PQ codes, TRQ codes, full vectors) live in
    capacity-padded device arrays mutated by append only.
    """

    def __init__(self, index: FaTRQIndex,
                 streaming: StreamingConfig | None = None):
        cfg = index.config
        scfg = streaming or StreamingConfig()
        n = int(index.x.shape[0])
        cap_rows = int(n * (1.0 + scfg.row_headroom)) + 1

        self.config: PipelineConfig = cfg
        self.scfg = scfg
        self.layout = index.layout
        self.codebook = index.codebook
        self.centroids = index.ivf.centroids
        self.nlist = index.ivf.nlist

        # device row store, capacity-padded
        self.pq_codes = _pad_rows(index.pq_codes, cap_rows)
        self.trq = trq_mod.TRQCodes(
            dim=index.trq.dim,
            levels=tuple(jax.tree.map(lambda a: _pad_rows(a, cap_rows), lv)
                         for lv in index.trq.levels),
            scalars=jax.tree.map(lambda a: _pad_rows(a, cap_rows),
                                 index.trq.scalars),
            model=index.trq.model)
        self.x = _pad_rows(index.x, cap_rows)

        # host index structures
        self.base_lists = np.asarray(index.ivf.lists).copy()
        self.base_len = np.asarray(index.ivf.list_len).copy()
        self.delta_lists = np.full((self.nlist, scfg.delta_page), -1,
                                   np.int32)
        self.delta_len = np.zeros((self.nlist,), np.int32)
        self.row_gid = np.full((cap_rows,), -1, np.int64)
        self.row_gid[:n] = np.arange(n)
        self.alive = np.zeros((cap_rows,), bool)
        self.alive[:n] = True

        self.n_rows = n                 # row-store high-water mark
        self.next_gid = n
        self.n_tombstones = 0
        self.generation = 0             # bumped on every mutation
        self._n_base = n                # rows ≥ _n_base are delta (graph)
        self._graph: np.ndarray | None = None   # lazily-built adjacency
        self._graph_degree = 16
        self._gid_row: dict[int, int] = {i: i for i in range(n)}
        self._assignment: np.ndarray | None = None   # list → shard
        self._n_shards: int | None = None
        self._dev_cache: dict | None = None
        self._snap_cache: tuple[int, FaTRQIndex, np.ndarray] | None = None
        self._ex_cache: dict = {}
        self._gen_hooks: list = []

    # ------------------------------------------------------------ stats

    @property
    def cap_rows(self) -> int:
        return int(self.x.shape[0])

    @property
    def n_live(self) -> int:
        return len(self._gid_row)

    @property
    def n_delta_rows(self) -> int:
        return int(self.delta_len.sum())

    def __len__(self) -> int:
        return self.n_live

    def stats(self) -> dict:
        live, tomb = self.n_live, self.n_tombstones
        return {"n_live": live, "n_rows": self.n_rows,
                "cap_rows": self.cap_rows, "n_delta_rows": self.n_delta_rows,
                "n_tombstones": tomb, "generation": self.generation,
                **self.drift()}

    def drift(self) -> dict:
        """The rebalance-trigger metrics (see ``needs_compaction``).

        ``shard_imbalance`` is the stale assignment's heaviest shard load
        over the heaviest load a FRESH ``lpt_assign`` on the current
        effective list lengths would achieve — i.e. the factor
        ``rebalance()`` could actually shrink it by.  Comparing against a
        lower bound on OPT instead would mis-trigger on workloads no
        partition can balance (few near-equal lists), spinning
        ``auto_compact`` on every mutation.
        """
        live, tomb = self.n_live, self.n_tombstones
        d = {"tombstone_frac": tomb / max(live + tomb, 1),
             "delta_frac": self.n_delta_rows / max(live, 1)}
        if self._assignment is not None:
            s = self._n_shards
            lens_eff = (self.base_len + self.delta_len).astype(np.int64)
            loads = np.bincount(self._assignment, weights=lens_eff,
                                minlength=s)
            _, fresh = lpt_assign(lens_eff, s)
            d["shard_imbalance"] = float(loads.max()) / max(
                float(fresh.max()), 1.0)
            d["lpt_bound"] = 4.0 / 3.0 - 1.0 / (3.0 * s)
        return d

    def needs_compaction(self) -> bool:
        """True once any drift metric crosses its threshold: tombstone
        fraction, delta fraction, or (with a live shard assignment) the
        heaviest shard exceeding what a fresh LPT partition would achieve
        by more than the LPT (4/3 − 1/3S) guarantee factor."""
        if self.n_live == 0:
            return False                    # nothing to fold or balance
        d = self.drift()
        if d["tombstone_frac"] > self.scfg.max_tombstone_frac:
            return True
        if d["delta_frac"] > self.scfg.max_delta_frac:
            return True
        if "shard_imbalance" in d and d["shard_imbalance"] > d["lpt_bound"]:
            return True
        return False

    # ---------------------------------------------------------- mutation

    def add_generation_hook(self, fn) -> None:
        """Register ``fn(index, generation)`` to fire after EVERY mutation
        that bumps the generation (``insert``/``delete``/``compact``/
        ``rebalance``).  Observers that key state on the generation — the
        serving layer's query-result cache (``serving.cache.ResultCache``)
        is the canonical one — use this to invalidate proactively instead
        of holding stale entries until their keys age out."""
        self._gen_hooks.append(fn)

    def _invalidate(self) -> None:
        self.generation += 1
        self._dev_cache = None
        self._snap_cache = None
        for fn in list(self._gen_hooks):
            fn(self, self.generation)

    def _observe_mutation(self, op: str, **attrs) -> None:
        """Mutation observability: always-on cheap metrics (mutation
        counter by op + tombstone/delta drift gauges), and — only when a
        tracer is active — an ``index.<op>`` event carrying the FULL
        drift picture (``drift()`` re-runs ``lpt_assign`` under a live
        shard assignment, too expensive for the untraced path)."""
        reg = obs_metrics.active()
        reg.counter("streaming_mutations_total", "index mutations by op",
                    labelnames=("op",)).labels(op=op).inc()
        live, tomb = self.n_live, self.n_tombstones
        reg.gauge("streaming_tombstone_frac",
                  "tombstoned fraction of tracked rows").set(
                      tomb / max(live + tomb, 1))
        reg.gauge("streaming_delta_frac",
                  "delta-page rows over live rows").set(
                      self.n_delta_rows / max(live, 1))
        if trace.active() is not None:
            payload = {"generation": self.generation, "n_live": live,
                       **self.drift()}
            payload.update(attrs)
            trace.event(f"index.{op}", track="index", **payload)

    def _grow_rows(self, need: int) -> None:
        new_cap = max(need, 2 * self.cap_rows)
        self.pq_codes = _pad_rows(self.pq_codes, new_cap)
        self.trq = trq_mod.TRQCodes(
            dim=self.trq.dim,
            levels=tuple(jax.tree.map(lambda a: _pad_rows(a, new_cap), lv)
                         for lv in self.trq.levels),
            scalars=jax.tree.map(lambda a: _pad_rows(a, new_cap),
                                 self.trq.scalars),
            model=self.trq.model)
        self.x = _pad_rows(self.x, new_cap)
        self.row_gid = np.concatenate(
            [self.row_gid, np.full(new_cap - len(self.row_gid), -1,
                                   np.int64)])
        self.alive = np.concatenate(
            [self.alive, np.zeros(new_cap - len(self.alive), bool)])

    def insert(self, x_new: jax.Array) -> np.ndarray:
        """Append a batch of vectors; returns their global ids.

        Assign to the nearest (frozen) centroid, PQ- and TRQ-encode ONLY
        the new rows, append them to the row store, and push their row ids
        onto the owning lists' delta pages (bucketized scatter, no Python
        loop).  O(batch) encode + append work — existing rows untouched.
        """
        x_new = jnp.asarray(x_new, jnp.float32)
        if x_new.ndim == 1:
            x_new = x_new[None]
        b = int(x_new.shape[0])
        if b == 0:
            return np.zeros((0,), np.int64)
        if self.n_rows + b > self.cap_rows:
            self._grow_rows(self.n_rows + b)

        list_ids = np.asarray(assign(x_new, self.centroids))
        pq = pq_mod.encode(self.codebook, x_new)
        x_c = pq_mod.decode(self.codebook, pq)
        new_trq = trq_mod.encode_rows(x_new, x_c,
                                      num_levels=self.config.trq_levels,
                                      model=self.trq.model)
        start = self.n_rows
        self.pq_codes = jax.lax.dynamic_update_slice(self.pq_codes, pq,
                                                     (start, 0))
        self.trq = trq_mod.write_rows(self.trq, new_trq, start)
        self.x = jax.lax.dynamic_update_slice(
            self.x, x_new.astype(self.x.dtype), (start, 0))

        rows = np.arange(start, start + b)
        gids = np.arange(self.next_gid, self.next_gid + b)
        self.row_gid[rows] = gids
        self.alive[rows] = True
        self._gid_row.update(zip(gids.tolist(), rows.tolist()))
        self.n_rows += b
        self.next_gid += b

        # online graph maintenance: wire the new rows into the adjacency
        # (only once a graph search has materialized it)
        if self._graph is not None:
            self._graph = graph_mod.insert_nodes(
                self._graph, np.asarray(self.x[: self.n_rows]), start)

        # delta append: bucketize the batch by list, grow pages if needed
        counts = np.bincount(list_ids, minlength=self.nlist).astype(np.int32)
        need = int((self.delta_len + counts).max())
        dcap = self.delta_lists.shape[1]
        if need > dcap:
            page = self.scfg.delta_page
            new_dcap = ((need + page - 1) // page) * page
            self.delta_lists = np.concatenate(
                [self.delta_lists,
                 np.full((self.nlist, new_dcap - dcap), -1, np.int32)],
                axis=1)
        order = np.argsort(list_ids, kind="stable")
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        pos = (np.arange(b) - starts[list_ids[order]]
               + self.delta_len[list_ids[order]])
        self.delta_lists[list_ids[order], pos] = rows[order]
        self.delta_len += counts

        self._invalidate()
        self._observe_mutation("insert", n=b)
        if self.scfg.auto_compact:
            self.maybe_compact()
        return gids

    def delete(self, gids) -> int:
        """Tombstone the given global ids (masked out of search until the
        next compaction).  Raises KeyError on unknown/already-deleted/
        duplicated ids BEFORE mutating anything, so a bad batch leaves the
        index untouched; returns the number of tombstones set."""
        gids = np.atleast_1d(np.asarray(gids, np.int64)).tolist()
        if len(set(gids)) != len(gids):
            raise KeyError(f"duplicate ids in delete batch of {len(gids)}")
        rows = [self._gid_row[g] for g in gids]   # KeyError: unknown id
        for g, row in zip(gids, rows):
            del self._gid_row[g]
            self.alive[row] = False
        self.n_tombstones += len(gids)
        self._invalidate()
        self._observe_mutation("delete", n=len(gids))
        if self.scfg.auto_compact:
            self.maybe_compact()
        return len(gids)

    # ------------------------------------------------- compact / rebalance

    def _live_assignment(self) -> tuple[np.ndarray, np.ndarray]:
        """(live rows in stable order, their list ids) — assignment
        recomputed from scratch against the frozen centroids, exactly what
        a static rebuild on the surviving rows would do (``assign`` is
        row-independent, so this also equals the tracked membership)."""
        live_rows = np.where(self.alive[: self.n_rows])[0]
        if live_rows.size == 0:
            raise ValueError("empty index: nothing to compact/search")
        list_ids = np.asarray(assign(self.x[jnp.asarray(live_rows)],
                                     self.centroids))
        return live_rows, list_ids

    def compact(self) -> dict:
        """Fold delta pages into base lists and drop tombstones.

        One gather repacks the row store to the surviving rows (stable
        order — global ids stay monotonic in row order); base lists are
        refilled with the vectorized ``ivf.fill_lists``; delta pages reset
        to one empty page.  No re-encode: TRQ codes are centroid-relative
        and move with their rows.
        """
        folded, dropped = self.n_delta_rows, self.n_tombstones
        x_old = np.asarray(self.x[: self.n_rows]) \
            if self._graph is not None else None
        live_rows, list_ids = self._live_assignment()
        n_live = live_rows.size
        cap = int(3.0 * n_live / self.nlist) + 1
        lists, lens, _ = ivf_mod.fill_lists(list_ids, self.nlist, cap)

        perm = jnp.asarray(live_rows)
        new_cap = int(n_live * (1.0 + self.scfg.row_headroom)) + 1
        self.pq_codes = _pad_rows(self.pq_codes[perm], new_cap)
        self.trq = trq_mod.TRQCodes(
            dim=self.trq.dim,
            levels=tuple(jax.tree.map(lambda a: _pad_rows(a[perm], new_cap),
                                      lv) for lv in self.trq.levels),
            scalars=jax.tree.map(lambda a: _pad_rows(a[perm], new_cap),
                                 self.trq.scalars),
            model=self.trq.model)
        self.x = _pad_rows(self.x[perm], new_cap)

        gids = self.row_gid[live_rows]
        self.row_gid = np.full((new_cap,), -1, np.int64)
        self.row_gid[:n_live] = gids
        self.alive = np.zeros((new_cap,), bool)
        self.alive[:n_live] = True
        self._gid_row = dict(zip(gids.tolist(), range(n_live)))

        self.base_lists, self.base_len = lists, lens
        self.delta_lists = np.full((self.nlist, self.scfg.delta_page), -1,
                                   np.int32)
        self.delta_len = np.zeros((self.nlist,), np.int32)
        self.n_rows = n_live
        self.n_tombstones = 0
        # graph maintenance: drop dead rows, patch edges through them with
        # the one-hop contraction; all surviving rows are base again
        if self._graph is not None:
            self._graph = graph_mod.compact_graph(self._graph, x_old,
                                                  live_rows)
        self._n_base = n_live
        self._invalidate()
        self._observe_mutation("compact", folded_delta_rows=folded,
                               dropped_tombstones=dropped)
        return {"folded_delta_rows": folded, "dropped_tombstones": dropped,
                "n_live": n_live}

    def rebalance(self, n_shards: int) -> dict:
        """Compact, then re-partition lists across ``n_shards`` with the
        same LPT greedy the static partitioner uses.  Reports how many
        rows MOVED shards relative to the previous assignment — a move is
        a gather of already-encoded packed codes (no re-encode)."""
        prev = self._assignment
        stats = self.compact()
        members, _ = lpt_assign(self.base_len, n_shards)
        assignment = np.empty((self.nlist,), np.int32)
        for s, m in enumerate(members):
            assignment[m] = s
        if prev is not None and self._n_shards == n_shards:
            moved_lists = np.nonzero(assignment != prev)[0]
            stats["moved_rows"] = int(self.base_len[moved_lists].sum())
        else:
            stats["moved_rows"] = int(self.base_len.sum())
        self._assignment = assignment
        self._n_shards = n_shards
        stats["shard_loads"] = [int(self.base_len[m].sum()) for m in members]
        self._invalidate()
        self._observe_mutation("rebalance", moved_rows=stats["moved_rows"],
                               shard_loads=stats["shard_loads"])
        return stats

    def maybe_compact(self) -> dict | None:
        """Drift-triggered fold: ``rebalance`` when a shard assignment is
        live, else ``compact``.  No-op (None) below the thresholds."""
        if not self.needs_compaction():
            return None
        if self._n_shards is not None:
            return self.rebalance(self._n_shards)
        return self.compact()

    # ----------------------------------------------------------- snapshot

    def rebuild_static(self) -> tuple[FaTRQIndex, np.ndarray]:
        """From-scratch static rebuild on the surviving rows.

        Reassigns every survivor into fresh inverted lists against the
        trained quantizers and gathers a dense row store — a plain
        ``FaTRQIndex`` (rebuilding the quantizers themselves on drifted
        data is a model update, out of index-maintenance scope).  Returns
        (index, gid) with ``gid[i]`` the global id of the static index's
        row ``i``; ``StreamingIndex.search`` matches its top-k exactly
        (see module docstring).  Cached per generation — also the
        snapshot behind ``search(shards=...)``.
        """
        if self._snap_cache is not None \
                and self._snap_cache[0] == self.generation:
            return self._snap_cache[1], self._snap_cache[2]
        live_rows, list_ids = self._live_assignment()
        cap = int(3.0 * live_rows.size / self.nlist) + 1
        lists, lens, _ = ivf_mod.fill_lists(list_ids, self.nlist, cap)
        perm = jnp.asarray(live_rows)
        idx = FaTRQIndex(
            config=self.config, codebook=self.codebook,
            pq_codes=self.pq_codes[perm],
            ivf=ivf_mod.IVFIndex(centroids=self.centroids,
                                 lists=jnp.asarray(lists),
                                 list_len=jnp.asarray(lens)),
            trq=trq_mod.gather_rows(self.trq, perm),
            x=self.x[perm])
        gid = self.row_gid[live_rows].copy()
        self._snap_cache = (self.generation, idx, gid)
        return idx, gid

    # ------------------------------------------------------------- search

    def _graph_host(self) -> np.ndarray:
        """The online-maintained adjacency over rows ``0..n_rows`` —
        including tombstoned rows (traversal routes through them until the
        next compaction).  Built once from the current row store on first
        graph search; ``insert``/``compact`` keep it wired incrementally
        from then on (never rebuilt)."""
        if self._graph is None:
            self._graph = np.asarray(graph_mod.build(
                self.x[: self.n_rows], degree=self._graph_degree).neighbors)
        return self._graph

    def _dev(self) -> dict:
        if self._dev_cache is None or \
                self._dev_cache["gen"] != self.generation:
            self._dev_cache = {
                "gen": self.generation,
                "base_lists": jnp.asarray(self.base_lists),
                "delta_lists": jnp.asarray(self.delta_lists),
                "alive": jnp.asarray(self.alive),
                "row_gid": jnp.asarray(self.row_gid),
            }
        return self._dev_cache

    def execute(self, queries: jax.Array, *, k: int | None = None,
                front: str | None = None, backend: str | None = None,
                micro_batch: int | None = None,
                refine_budget: int | None = None,
                cost: QueryCost | None = None, shards: int | None = None
                ) -> tuple[jax.Array, jax.Array, QueryCost]:
        """Generation-aware FaTRQ search → (Q, k) GLOBAL ids, (Q, k) exact
        squared-L2 distances, and the traffic ledger.

        The IVF front probes base ∪ delta lists and masks tombstones; the
        graph front beam-searches the online-maintained adjacency with the
        same masking.  Both refine backends score base and delta rows under
        one QueryCost (delta traffic on its own ``delta:cxl`` entry).
        ``shards`` routes a static snapshot through ``anns.sharding`` (with
        the requested front) and maps the results back to global ids.
        """
        cfg = self.config
        k = k or cfg.final_k
        front = front or "ivf"
        backend = backend or cfg.backend
        micro_batch = micro_batch if micro_batch is not None \
            else cfg.micro_batch

        if shards is not None:
            from repro.anns.sharding import make_sharded_executor
            idx, gid = self.rebuild_static()
            sx = make_sharded_executor(idx, shards=shards, front=front,
                                       backend=backend,
                                       micro_batch=micro_batch,
                                       refine_budget=refine_budget)
            ids, dists, scost = sx.execute(queries, k=k, cost=cost)
            return jnp.asarray(gid)[ids], dists, scost

        dev = self._dev()
        ex = self._executor(front, backend, micro_batch, dev,
                            refine_budget=refine_budget)
        rows, dists, out_cost = ex.execute(queries, k=k, cost=cost)
        return dev["row_gid"][rows], dists, out_cost

    def search(self, queries: jax.Array, *, k: int | None = None,
               front: str | None = None, backend: str | None = None,
               micro_batch: int | None = None,
               cost: QueryCost | None = None, shards: int | None = None
               ) -> tuple[jax.Array, QueryCost]:
        """Legacy tuple surface over ``execute`` (no distances)."""
        ids, _, out_cost = self.execute(queries, k=k, front=front,
                                        backend=backend,
                                        micro_batch=micro_batch, cost=cost,
                                        shards=shards)
        return ids, out_cost

    def _executor(self, front: str, backend: str, micro_batch: int | None,
                  dev: dict,
                  refine_budget: int | None = None) -> SearchExecutor:
        """Plain ``SearchExecutor`` over the current generation — the
        streaming fronts satisfy the ``FrontStage`` protocol and
        ``StreamingIndex`` quacks like a ``FaTRQIndex`` (``config``,
        ``layout``, ``trq``, ``x``), so search/fold logic lives in ONE
        place.  Front and backend come from the capability registry
        (``anns.registry``); cached per (generation, front, backend,
        micro_batch, refine_budget)."""
        key = (dev["gen"], front, backend, micro_batch, refine_budget)
        ex = self._ex_cache.get(key)
        if ex is not None:
            return ex
        be = registry.make_backend(backend)
        fs = registry.make_front(front, "streaming", self)
        ex = SearchExecutor(index=self, front=fs, backend=be,
                            micro_batch=micro_batch,
                            refine_budget=refine_budget)
        # keep only the current generation's executors (stale fronts hold
        # references to superseded device arrays)
        self._ex_cache = {kk: v for kk, v in self._ex_cache.items()
                          if kk[0] == dev["gen"]}
        self._ex_cache[key] = ex
        return ex


# ----------------------------------------------------- registry integration
# Both fronts declare streaming support in ``anns.stages``; the factories
# building their generation-aware physical variants live here, next to the
# stages.


def make_streaming_front(st: StreamingIndex, **opts) -> StreamingFrontStage:
    nprobe = opts.pop("nprobe", st.config.nprobe)
    if opts:
        raise TypeError(f"unknown streaming front options: {sorted(opts)}")
    dev = st._dev()
    return StreamingFrontStage(
        centroids=st.centroids, codebook=st.codebook, pq_codes=st.pq_codes,
        base_lists=dev["base_lists"], delta_lists=dev["delta_lists"],
        alive=dev["alive"], nprobe=nprobe)


def make_streaming_graph_front(st: StreamingIndex,
                               **opts) -> GraphStreamingFrontStage:
    """Materialize (or reuse) the online-maintained adjacency and bind the
    current generation's alive bitmap + delta boundary to the stage."""
    degree = opts.pop("degree", st._graph_degree)
    if degree != st._graph_degree and st._graph is not None:
        raise ValueError(f"streaming graph was materialized at degree "
                         f"{st._graph_degree}, cannot serve degree {degree}")
    st._graph_degree = degree
    nb = st._graph_host()
    return GraphStreamingFrontStage(
        graph=graph_mod.GraphIndex(neighbors=jnp.asarray(nb)),
        codebook=st.codebook, pq_codes=st.pq_codes[: st.n_rows],
        alive=jnp.asarray(st.alive[: st.n_rows]), n_base=st._n_base, **opts)


registry.add_front_factory("ivf", "streaming", make_streaming_front)
registry.add_front_factory("graph", "streaming", make_streaming_graph_front)
