"""Pluggable search stages for the staged executor (paper Fig. 5).

The search path is an explicit three-stage pipeline over query
micro-batches — no per-query Python closures anywhere:

  front   : candidate generation + coarse ADC scoring in fast memory.
            Two interchangeable implementations: ``IVFFrontStage`` (inverted
            lists, the paper's primary front) and ``GraphFrontStage``
            (CAGRA-style beam search over PQ reconstructions).
  refine  : FaTRQ progressive estimation over the candidate batch, streaming
            packed ternary codes from far memory.  Two backends with
            identical semantics: ``ReferenceRefineBackend`` (pure-jnp
            ``core.estimator`` / ``trq.progressive_search`` math) and
            ``PallasRefineBackend`` (the persistent
            ``kernels.ternary_refine_fused`` kernel: ALL TRQ levels, the
            certified bounds, the alive-mask chain and the per-level
            survivor counters in one ``pallas_call`` per micro-batch).
  rerank  : survivors fetch full-precision vectors ("SSD") for exact L2.

Every stage returns *device-side* counters (0-d int32 arrays) alongside its
arrays; the executor folds them into a ``memory.QueryCost`` ledger with one
host transfer per search call (see ``executor.py``).  Stages also own their
traffic model via ``fold_cost`` so the executor stays backend-agnostic.

The streaming subsystem (``anns.streaming``) reuses the same pieces: its
generation-aware fronts (base ∪ delta IVF probe, tombstone-aware graph
traversal) emit the extra ``delta_cand`` counter (delta-row candidates,
billed to a distinct far-memory ledger entry) and both refine backends
score base and delta rows in one candidate batch — the
``Candidates``/``Refined`` contracts are unchanged.  The sharded
subsystem (``anns.sharding``) likewise inlines both fronts in its
shard_map body through ``registry.ShardedFrontHooks``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.anns import registry
from repro.core import trq as trq_mod
from repro.core.estimator import pooled_k_smallest
from repro.core.trq import TRQCodes
from repro.index import graph as graph_mod
from repro.index import ivf as ivf_mod
from repro.kernels import ops as kernel_ops
from repro.kernels import pq_adc
from repro.memory import QueryCost, RecordLayout, Tier
from repro.quant import pq as pq_mod

Counters = dict[str, jax.Array]     # name → 0-d device counter


class Candidates(NamedTuple):
    """Front-stage output for a query micro-batch.

    ``is_delta`` marks candidates living in delta spill pages (streaming
    fronts populate it; static/sharded fronts leave it ``None``) so the
    refine backends can split per-level survivor traffic for the ledger.

    ``tier`` carries per-candidate placement codes (``memory.placement``
    TIER_* values) on the tiered layout; every other front leaves it
    ``None``.  The executor — not the refine backends — consumes it: hot
    candidates detour to exact HBM scoring, cold candidates' residual
    stream is re-billed at SSD rates via ``is_delta``-style marking.
    """

    ids: jax.Array        # (Q, C) int32, clamped ≥ 0
    valid: jax.Array      # (Q, C) bool
    d0: jax.Array         # (Q, C) f32 coarse ADC distance, +inf if invalid
    counters: Counters
    is_delta: jax.Array | None = None   # (Q, C) bool, or None
    tier: jax.Array | None = None       # (Q, C) int8 TIER_* codes, or None


class Refined(NamedTuple):
    """Refine-stage output: calibrated estimates + survivor mask."""

    est: jax.Array        # (Q, C) f32
    alive: jax.Array      # (Q, C) bool (already ∧ valid)
    counters: Counters


@runtime_checkable
class FrontStage(Protocol):
    """Candidate generation: batched queries in, Candidates out.

    ``qvalid`` is an optional per-query validity mask (Q,) used by the
    bucket-padded entry points (``executor.pad_chunk``): padded query rows
    must contribute NOTHING to the candidate set or the device-side
    counters, so batched ledgers stay bit-identical to the sum of the
    real queries' unpadded ledgers.  ``None`` means all queries are real
    (the legacy trace).
    """

    name: str

    def candidates(self, queries: jax.Array,
                   qvalid: jax.Array | None = None) -> Candidates: ...

    def fold_cost(self, cost: QueryCost, counts: dict[str, int],
                  layout: RecordLayout) -> None: ...


@runtime_checkable
class RefineBackend(Protocol):
    """FaTRQ refinement over a candidate batch.

    ``axis_name`` selects sharded operation: inside ``shard_map`` the
    pruning thresholds are computed globally across the named mesh axis so
    per-shard survivor masks match an unsharded run exactly (see
    ``anns.sharding``).
    """

    name: str

    def refine(self, queries: jax.Array, cand: Candidates, trq: TRQCodes,
               *, k: int, bound: str, z: float,
               axis_name: str | None = None) -> Refined: ...


# ------------------------------------------------------------- front stages


def fold_ivf_front_cost(cost: QueryCost, counts: dict[str, int],
                        layout: RecordLayout) -> None:
    """IVF front traffic model: PQ codes + LUT live in fast memory (HBM).

    Shared by ``IVFFrontStage.fold_cost``, the per-shard fold in
    ``anns.sharding``, and the streaming front in ``anns.streaming`` (both
    are IVF-only), so the ledgers cannot drift apart.  ``front_cand``
    counts base AND delta candidates — delta rows' PQ codes are appended
    into the same fast-memory store; only their far-memory stream is
    billed separately (the ``delta_cand`` counter in
    ``executor.fold_counts``).
    """
    cost.record("coarse", Tier.HBM, counts["front_cand"], layout.fast_bytes)


# Device work of each layer runs under a stable ``jax.named_scope``
# (``fatrq.front.probe``, ``fatrq.front.adc``, ``fatrq.refine.gather``,
# ``fatrq.refine.kernel``, ``fatrq.rerank``).  A scope changes only the op
# names in the HLO metadata, which a profile carries as each device op's
# path, so the layer of an op survives any change of jit boundaries.


def rank_centroid_lists(centroids: jax.Array, queries: jax.Array, *,
                        nprobe: int) -> tuple[jax.Array, jax.Array]:
    """Squared-L2 centroid ranking → (distances (Q, nlist), global
    top-nprobe list ids (Q, nprobe)).

    Shared by the unsharded IVF front and the sharded front
    (``anns.sharding``) — the sharded path's bit-identical guarantee
    depends on both selecting the same probe set.
    """
    with jax.named_scope("fatrq.front.probe"):
        d = jnp.sum((queries[:, None, :] - centroids[None]) ** 2, axis=-1)
        _, top_lists = jax.lax.top_k(-d, nprobe)
    return d, top_lists


def adc_score(codebook: pq_mod.PQCodebook, pq_codes: jax.Array,
              ids: jax.Array, queries: jax.Array,
              valid: jax.Array) -> jax.Array:
    """Batched PQ-ADC scoring of per-query candidates ``ids`` (Q, C):
    gathers their PQ codes (Q, C, M) and scores them, +inf outside
    ``valid``.  Shared by every front, sharded or not.  On a TPU the
    scores come from the one-hot MXU kernel (``kernels/pq_adc.py``),
    elsewhere from the table gather of ``quant.pq``."""
    with jax.named_scope("fatrq.front.adc"):
        codes = pq_codes[ids]
        tables = jax.vmap(lambda q: pq_mod.adc_table(codebook, q))(queries)
        if pq_adc.use_kernel():
            d0 = pq_adc.pq_adc_batch(codes, tables)
        else:
            d0 = jax.vmap(pq_mod.adc_distances)(tables, codes)
        return jnp.where(valid, d0, jnp.inf)


@partial(jax.jit, static_argnames=("nprobe",))
def _ivf_candidates(ivf: ivf_mod.IVFIndex, codebook, pq_codes, queries,
                    qvalid, *, nprobe: int):
    _, top_lists = rank_centroid_lists(ivf.centroids, queries,
                                       nprobe=nprobe)
    with jax.named_scope("fatrq.front.probe"):
        ids = ivf.lists[top_lists].reshape(queries.shape[0], -1)
    valid = ids >= 0                       # ids: (Q, nprobe·cap)
    if qvalid is not None:                 # padded rows: no candidates
        valid = valid & qvalid[:, None]
    safe = jnp.maximum(ids, 0)
    d0 = adc_score(codebook, pq_codes, safe, queries, valid)
    return safe, valid, d0, jnp.sum(valid)


@dataclass
class IVFFrontStage:
    """Inverted-file probe + PQ-ADC scoring (the paper's primary front)."""

    ivf: ivf_mod.IVFIndex
    codebook: pq_mod.PQCodebook
    pq_codes: jax.Array
    nprobe: int = 8
    name: str = field(default="ivf", init=False)

    def candidates(self, queries: jax.Array,
                   qvalid: jax.Array | None = None) -> Candidates:
        safe, valid, d0, n_cand = _ivf_candidates(
            self.ivf, self.codebook, self.pq_codes, queries, qvalid,
            nprobe=self.nprobe)
        return Candidates(ids=safe, valid=valid, d0=d0,
                          counters={"front_cand": n_cand})

    def fold_cost(self, cost: QueryCost, counts: dict[str, int],
                  layout: RecordLayout) -> None:
        fold_ivf_front_cost(cost, counts, layout)


@partial(jax.jit, static_argnames=("iters", "beam", "expand"))
def _graph_candidates(neighbors, x_score, codebook, pq_codes, queries,
                      qvalid, *, iters: int, beam: int, expand: int):
    gidx = graph_mod.GraphIndex(neighbors=neighbors)
    ids = jax.vmap(lambda q: graph_mod.search(gidx, x_score, q, iters=iters,
                                              beam=beam, expand=expand))(
        queries)                                              # (Q, beam)
    valid = jnp.ones(ids.shape, bool) if qvalid is None \
        else jnp.broadcast_to(qvalid[:, None], ids.shape)
    d0 = adc_score(codebook, pq_codes, ids, queries, valid)
    return ids, valid, d0, jnp.sum(valid)


def fold_graph_front_cost(cost: QueryCost, counts: dict[str, int],
                          layout: RecordLayout) -> None:
    """Graph front traffic model: beam traversal decodes PQ codes of the
    visited neighborhoods (``front_hops``), then the final beam is
    ADC-scored (``front_cand``) — all fast-memory traffic.  Shared by
    ``GraphFrontStage.fold_cost``, the per-shard fold in ``anns.sharding``
    and the streaming graph front (``anns.streaming``), so the three
    datapaths' ledgers cannot drift apart."""
    cost.record("front", Tier.HBM, counts["front_hops"], layout.fast_bytes)
    cost.record("coarse", Tier.HBM, counts["front_cand"], layout.fast_bytes)


@dataclass
class GraphFrontStage:
    """CAGRA-style beam search scored on PQ reconstructions.

    Traversal distances use the fast-memory PQ decode (no SSD touches); the
    resulting beam is handed to refinement exactly like an IVF candidate
    list.  ``hops`` counts graph-adjacency PQ fetches during traversal.
    """

    graph: graph_mod.GraphIndex
    codebook: pq_mod.PQCodebook
    pq_codes: jax.Array
    beam: int = 64
    iters: int = 32
    expand: int = 4
    name: str = field(default="graph", init=False)
    x_score: jax.Array = field(init=False)

    def __post_init__(self):
        self.x_score = pq_mod.decode(self.codebook, self.pq_codes)

    def candidates(self, queries: jax.Array,
                   qvalid: jax.Array | None = None) -> Candidates:
        ids, valid, d0, n_cand = _graph_candidates(
            self.graph.neighbors, self.x_score, self.codebook, self.pq_codes,
            queries, qvalid, iters=self.iters, beam=self.beam,
            expand=self.expand)
        # traversal work is uniform per query, so padded rows just scale out
        per_q = self.iters * self.expand * self.graph.degree
        nq = jnp.asarray(queries.shape[0], jnp.int32) if qvalid is None \
            else jnp.sum(qvalid).astype(jnp.int32)
        return Candidates(ids=ids, valid=valid, d0=d0,
                          counters={"front_cand": n_cand,
                                    "front_hops": nq * per_q})

    def fold_cost(self, cost: QueryCost, counts: dict[str, int],
                  layout: RecordLayout) -> None:
        fold_graph_front_cost(cost, counts, layout)


# ---------------------------------------------------------- refine backends


def _level_counters(level_alive: tuple[jax.Array, ...],
                    is_delta: jax.Array | None = None) -> Counters:
    """Per-level survivor counters from the alive-mask chain.

    ``refine_alive`` is the FINAL survivor count (kept for the single-level
    ledger and back-compat); ``refine_alive_l{ℓ}`` counts the candidates
    ENTERING level ℓ ≥ 1 — i.e. survivors of level ℓ−1 — which is exactly
    the population whose level-ℓ codes stream from far memory.  When the
    front marks delta-page candidates, ``refine_alive_l{ℓ}_delta`` is the
    delta-resident share of that population, so the executor can bill it
    to the delta spill stream instead of the base residual store.
    """
    counters: Counters = {"refine_alive": jnp.sum(level_alive[-1])}
    for lv in range(1, len(level_alive)):
        counters[f"refine_alive_l{lv}"] = jnp.sum(level_alive[lv - 1])
        if is_delta is not None:
            counters[f"refine_alive_l{lv}_delta"] = jnp.sum(
                level_alive[lv - 1] & is_delta)
    return counters


@partial(jax.jit, static_argnames=("k", "bound", "z", "axis_name"))
def _reference_refine(queries, d0, ids, valid, trq: TRQCodes, *, k: int,
                      bound: str, z: float, axis_name: str | None = None):
    def one(q, d0_q, ids_q):
        state, level_alive = trq_mod.progressive_search(
            q, d0_q, trq, ids_q, k=k, bound=bound, z=z, axis_name=axis_name,
            collect_level_alive=True)
        return state.est, level_alive

    est, level_alive = jax.vmap(one)(queries, d0, ids)
    level_alive = tuple(a & valid for a in level_alive)
    return est, level_alive


@dataclass
class ReferenceRefineBackend:
    """Pure-jnp estimator path (``core.estimator`` via progressive_search)."""

    name: str = field(default="reference", init=False)

    def refine(self, queries: jax.Array, cand: Candidates, trq: TRQCodes,
               *, k: int, bound: str, z: float,
               axis_name: str | None = None) -> Refined:
        est, level_alive = _reference_refine(
            queries, cand.d0, cand.ids, cand.valid, trq, k=k, bound=bound,
            z=z, axis_name=axis_name)
        return Refined(est=est, alive=level_alive[-1],
                       counters=_level_counters(level_alive, cand.is_delta))


def _topk_threshold_batch(hi: jax.Array, alive: jax.Array, k: int,
                          axis_name: str | None = None) -> jax.Array:
    """Batched kth-smallest upper estimate among alive candidates (Q,).

    With ``axis_name`` (inside shard_map) the threshold is global — the
    shared ``estimator.pooled_k_smallest`` pooling, batched over queries.
    """
    masked = jnp.where(alive, hi, jnp.inf)
    return pooled_k_smallest(masked, k, axis_name)


@partial(jax.jit, static_argnames=("k", "bound", "z", "block_c",
                                   "axis_name"))
def _pallas_refine(queries, d0, ids, valid, is_delta, trq: TRQCodes, *,
                   k: int, bound: str, z: float, block_c: int,
                   axis_name: str | None = None):
    """Persistent fused refinement: ONE pallas_call per query micro-batch.

    All TRQ levels' packed codes and [proj, norm, rho] planes are gathered
    up front; the kernel walks them level-by-level with the running
    estimate / certified bounds / alive mask resident in VMEM scratch, so
    no intermediate estimates or masks round-trip through HBM.

    Unsharded (``axis_name=None``): the pruning threshold after each level
    is computed on-chip (SMEM carry) and the kernel directly returns the
    final estimates, survivor mask and per-level survivor counts.

    Sharded (inside shard_map): thresholds must be globally exact, so the
    kernel's bounds-emitting form returns every level's certified
    (lo, hi) from the same single launch and the alive chain runs here
    with ``pooled_k_smallest`` exchanging thresholds across ``axis_name``
    between level segments — bit-identical masks to the on-chip form.
    """
    sc = trq.scalars
    with jax.named_scope("fatrq.refine.gather"):
        packed_levels = jnp.stack([lv.packed[ids] for lv in trq.levels])
        lvl_proj = jnp.stack([lv.proj[ids] for lv in trq.levels])
        lvl_norm = jnp.stack([lv.norm[ids] for lv in trq.levels])
        lvl_rho = jnp.stack([lv.rho[ids] for lv in trq.levels])
        delta_mask = jnp.zeros_like(valid) if is_delta is None else is_delta
        args = (packed_levels, queries, d0, sc.delta_sq[ids], sc.cross[ids],
                sc.norm[ids], sc.rho[ids], valid, delta_mask, lvl_proj,
                lvl_norm, lvl_rho, trq.model.w, trq.model.bias,
                trq.model.resid_std, z)

    if axis_name is None:
        with jax.named_scope("fatrq.refine.kernel"):
            est, alive, counts = kernel_ops.fused_refine_scores_batch(
                *args, k=k, bound=bound, block_c=block_c)
        nl = trq.num_levels
        counters: Counters = {"refine_alive": jnp.sum(counts[:, nl - 1])}
        for lv in range(1, nl):
            counters[f"refine_alive_l{lv}"] = jnp.sum(counts[:, lv - 1])
            if is_delta is not None:
                counters[f"refine_alive_l{lv}_delta"] = jnp.sum(
                    counts[:, nl + lv - 1])
        return est, alive, counters

    with jax.named_scope("fatrq.refine.kernel"):
        est, lo, hi = kernel_ops.fused_refine_bounds_batch(
            *args, bound=bound, block_c=block_c)
    alive = valid
    level_alive = []
    for lv in range(trq.num_levels):
        tau = _topk_threshold_batch(hi[:, lv], alive, k, axis_name)
        alive = alive & (lo[:, lv] <= tau[:, None])
        level_alive.append(alive)
    return est, alive, _level_counters(tuple(level_alive), is_delta)


@dataclass
class PallasRefineBackend:
    """Persistent fused-kernel path (``kernels.ternary_refine_fused``).

    The whole progressive-refinement loop — digit-plane unpack, per-level
    estimate stacking, certified margins, pruning thresholds, survivor
    masks and ledger counters — runs as a single ``pallas_call`` per query
    micro-batch (per shard when sharded).  Produces the same survivors and
    ledger as the reference backend; on CPU containers the kernel runs in
    interpret mode.
    """

    block_c: int = 512
    name: str = field(default="pallas", init=False)

    def refine(self, queries: jax.Array, cand: Candidates, trq: TRQCodes,
               *, k: int, bound: str, z: float,
               axis_name: str | None = None) -> Refined:
        est, alive, counters = _pallas_refine(
            queries, cand.d0, cand.ids, cand.valid, cand.is_delta, trq,
            k=k, bound=bound, z=z, block_c=self.block_c,
            axis_name=axis_name)
        return Refined(est=est, alive=alive, counters=counters)


# ----------------------------------------------------------------- rerank


@partial(jax.jit, static_argnames=("k", "budget"))
def _rerank_survivors(x, queries, ids, est, alive, *, k: int, budget: int):
    """Batched exact rerank: top-`budget` survivors by estimate fetch full
    vectors, exact L2, top-k.  Returns (topk_ids, topk_dists, n_ssd) —
    distances are the exact squared L2 of each returned id (+inf on padded
    slots when fewer than k candidates survived)."""
    with jax.named_scope("fatrq.rerank"):
        est_m = jnp.where(alive, est, jnp.inf)
        _, order = jax.lax.top_k(-est_m, budget)              # (Q, budget)
        fetch_ids = jnp.take_along_axis(ids, order, axis=1)
        fetch_alive = jnp.take_along_axis(alive, order, axis=1)
        d = jnp.sum((x[fetch_ids] - queries[:, None, :]) ** 2, axis=-1)
        d = jnp.where(fetch_alive, d, jnp.inf)
        neg_d, best = jax.lax.top_k(-d, k)
        topk = jnp.take_along_axis(fetch_ids, best, axis=1)
        return topk, -neg_d, jnp.sum(fetch_alive)


@jax.jit
def _score_hot(x, queries, ids, hot):
    """Exact squared-L2 for hot (HBM-resident) candidates, +inf elsewhere.
    The tiered layout's direct scoring path: full-precision rows of hot
    lists never left fast memory, so reading them costs HBM rates and the
    refinement cascade is skipped entirely for these candidates."""
    d = jnp.sum((x[ids] - queries[:, None, :]) ** 2, axis=-1)
    return jnp.where(hot, d, jnp.inf)


@partial(jax.jit, static_argnames=("k", "budget"))
def _rerank_survivors_tiered(x, queries, ids, est, alive, hot, *, k: int,
                             budget: int):
    """``_rerank_survivors`` for the tiered layout: identical ids and
    distances, but hot candidates' full vectors are already HBM-resident —
    their fetches must not bill to the SSD rerank counter.  Returns
    (topk_ids, topk_dists, n_ssd, n_hot_fetch)."""
    with jax.named_scope("fatrq.rerank"):
        est_m = jnp.where(alive, est, jnp.inf)
        _, order = jax.lax.top_k(-est_m, budget)
        fetch_ids = jnp.take_along_axis(ids, order, axis=1)
        fetch_alive = jnp.take_along_axis(alive, order, axis=1)
        fetch_hot = jnp.take_along_axis(hot, order, axis=1) & fetch_alive
        d = jnp.sum((x[fetch_ids] - queries[:, None, :]) ** 2, axis=-1)
        d = jnp.where(fetch_alive, d, jnp.inf)
        neg_d, best = jax.lax.top_k(-d, k)
        topk = jnp.take_along_axis(fetch_ids, best, axis=1)
        return (topk, -neg_d, jnp.sum(fetch_alive & ~fetch_hot),
                jnp.sum(fetch_hot))


@partial(jax.jit, static_argnames=("k",))
def _rerank_all(x, queries, ids, valid, *, k: int):
    """Baseline rerank: exact L2 over the whole candidate list (no refine).
    Returns (topk_ids, topk_dists, n_valid)."""
    d = jnp.sum((x[ids] - queries[:, None, :]) ** 2, axis=-1)
    d = jnp.where(valid, d, jnp.inf)
    neg_d, best = jax.lax.top_k(-d, k)
    return jnp.take_along_axis(ids, best, axis=1), -neg_d, jnp.sum(valid)


# ----------------------------------------------- front factories + registry
# Each front registers itself with the capability registry: supported index
# layouts plus a per-layout stage factory.  ``anns.streaming`` attaches the
# "streaming" factories (base ∪ delta IVF, tombstone-aware graph) and
# ``anns.tiered`` the "tiered" ones (tier-annotating wrappers) when they
# are imported; the "sharded" layout inlines its fronts in the shard_map
# body via ``registry.ShardedFrontHooks`` (``anns.sharding`` registers the
# whole-list LPT partitioner for IVF and the vector-range + halo
# partitioner for graph), so both fronts declare it here but register no
# stage factory for it.


def graph_for(index, *, degree: int = 16) -> graph_mod.GraphIndex:
    """Build (once per degree) and cache the kNN graph for an index's
    database.  The cache lives ON the index instance, so its lifetime is
    exactly the index's lifetime — no process-global registry to leak.
    Keyed by ``degree``: a degree-32 request must not silently return a
    previously cached degree-16 graph."""
    cache = getattr(index, "_graph_cache", None)
    if not isinstance(cache, dict):      # also migrates the pre-dict cache
        cache = {}
        index._graph_cache = cache
    g = cache.get(degree)
    if g is None:
        g = graph_mod.build(index.x, degree=degree)
        cache[degree] = g
    return g


def make_ivf_front(index, **opts) -> IVFFrontStage:
    nprobe = opts.pop("nprobe", index.config.nprobe)
    if opts:
        raise TypeError(f"unknown IVF front options: {sorted(opts)}")
    return IVFFrontStage(ivf=index.ivf, codebook=index.codebook,
                         pq_codes=index.pq_codes, nprobe=nprobe)


def make_graph_front(index, *, graph_index=None, degree: int = 16,
                     **opts) -> GraphFrontStage:
    g = graph_index if graph_index is not None \
        else graph_for(index, degree=degree)
    return GraphFrontStage(graph=g, codebook=index.codebook,
                           pq_codes=index.pq_codes, **opts)


registry.register_front("ivf",
                        layouts=("static", "sharded", "streaming", "tiered"),
                        make={"static": make_ivf_front})
registry.register_front("graph",
                        layouts=("static", "sharded", "streaming", "tiered"),
                        make={"static": make_graph_front})
registry.register_backend("reference", make=ReferenceRefineBackend)
registry.register_backend("pallas", make=PallasRefineBackend)
