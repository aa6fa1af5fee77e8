"""Staged search executor: front → refine → rerank over query micro-batches.

``SearchExecutor`` composes the pluggable stages defined in ``stages.py``
into the paper's pipelined datapath (Fig. 5) and owns the cost accounting:
each stage emits device-side counters (0-d int32 arrays), the executor
accumulates them across micro-batches *on device*, and a single host
transfer at the end of ``search`` folds the totals into a
``memory.QueryCost`` ledger — replacing the per-stage ``int(jnp.sum(...))``
round-trips the old monolithic pipeline did.

Construction is cheap (stages hold references to index arrays; all device
functions are module-level jits, so compilation caches globally), except
``front="graph"`` which builds the kNN graph on first use and caches it on
the index per degree (``stages.graph_for``).  ``make_executor`` memoizes
executors per index so facade callers (``anns.pipeline``, ``serving``) can
call it per search.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.anns import registry, stages as stages_mod
from repro.anns.stages import (Counters, FrontStage, RefineBackend,
                               graph_for as _graph_for)  # noqa: F401 - compat
from repro.index import graph as graph_mod
from repro.memory import QueryCost, Tier
from repro.memory.placement import TIER_COLD, TIER_HOT
from repro.obs import trace

# import-time snapshots of the capability registry, kept as module
# constants for pre-registry callers (stages.py has registered the
# built-ins by this point).  Stages registered later are visible only via
# anns.registry.front_names()/backend_names() — consult those for the
# live set.
FRONT_STAGES = registry.front_names()
REFINE_BACKENDS = registry.backend_names()

# measured scale of ADC + ternary adds per candidate (see benchmarks)
_COMPUTE_S_PER_CAND = 1e-7


def _accumulate(total: Counters, new: Counters) -> Counters:
    for name, v in new.items():
        total[name] = total[name] + v if name in total else v
    return total


def search_budget(config, k: int, override: int | None = None) -> int:
    """SSD rerank budget for a search call: the configured budget, with a
    4k/32 default, floored at k (k results need ≥ k fetches).  Shared by
    the unsharded and sharded executors — their top-k equivalence depends
    on deriving the SAME budget.  ``override`` is a plan-level budget
    (``QueryPlan.refine_budget``) taking precedence over the config's."""
    return max(override or config.refine_budget or max(4 * k, 32), k)


def iter_chunks(queries: jax.Array, micro_batch: int | None):
    """Split a query batch into device-sized micro-batches (None = all)."""
    if micro_batch is None or micro_batch >= queries.shape[0]:
        yield queries
        return
    for i in range(0, queries.shape[0], micro_batch):
        yield queries[i:i + micro_batch]


def bucket_for(n: int, micro_batch: int | None = None) -> int:
    """Smallest compiled batch bucket covering ``n`` queries.

    Buckets are powers of two, capped at ``micro_batch`` (the full-chunk
    shape, which is always compiled anyway).  Padding ragged chunks up to
    a bucket keeps the set of traced query shapes at
    {1, 2, 4, ..., micro_batch} regardless of caller batch sizes, so a
    serving layer coalescing variable-size request batches NEVER
    recompiles the stage jits per batch."""
    b = 1
    while b < n:
        b <<= 1
    if micro_batch is not None and b > micro_batch >= n:
        b = micro_batch
    return b


def pad_chunk(chunk: jax.Array, bucket: int
              ) -> tuple[jax.Array, jax.Array]:
    """Zero-pad a (n, D) chunk to ``bucket`` rows; returns the padded
    chunk plus the (bucket,) per-query validity mask.  The mask is always
    a device ARRAY (all-True when n == bucket) so full and padded batches
    of the same bucket share one trace."""
    n = chunk.shape[0]
    qvalid = jnp.arange(bucket) < n
    if n == bucket:
        return chunk, qvalid
    pad = jnp.zeros((bucket - n,) + chunk.shape[1:], chunk.dtype)
    return jnp.concatenate([chunk, pad], axis=0), qvalid


def _collect(counters: Counters) -> dict:
    """The single device→host transfer of a search call.  Scalar counters
    come back as Python ints; vector counters (the tiered layout's
    per-list ``list_heat`` histogram) as numpy arrays."""
    with trace.span("wait", track="query"):
        vals = jax.device_get(list(counters.values()))
    out = {}
    for n, v in zip(counters, vals):
        a = np.asarray(v)
        out[n] = int(a) if a.ndim == 0 else a
    return out


def _cat(parts: list[jax.Array]) -> jax.Array:
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


@dataclass
class SearchExecutor:
    """Batched staged search over a FaTRQIndex."""

    index: "FaTRQIndex"              # noqa: F821 - import cycle via pipeline
    front: FrontStage
    backend: RefineBackend
    micro_batch: int | None = None   # queries per device step; None = all
    refine_budget: int | None = None  # plan-level SSD budget override

    # -- construction -----------------------------------------------------

    @classmethod
    def from_index(cls, index, *, front: str = "ivf",
                   backend: str = "reference",
                   micro_batch: int | None = None,
                   refine_budget: int | None = None,
                   graph_index: graph_mod.GraphIndex | None = None,
                   layout: str = "static",
                   **front_opts) -> "SearchExecutor":
        if graph_index is not None:
            front_opts["graph_index"] = graph_index
        fs = registry.make_front(front, layout, index, **front_opts)
        be = registry.make_backend(backend)
        return cls(index=index, front=fs, backend=be,
                   micro_batch=micro_batch, refine_budget=refine_budget)

    # -- search -----------------------------------------------------------

    def _chunks(self, queries: jax.Array):
        return iter_chunks(queries, self.micro_batch)

    def _refine_rerank(self, chunk: jax.Array, cand, *, k: int, budget: int
                       ) -> tuple[jax.Array, jax.Array, Counters]:
        """Refine + SSD rerank over a front-stage result: the shared tail
        of ``execute`` and ``run_finish``."""
        cfg = self.index.config
        hot = cold = None
        rcand = cand
        if cand.tier is not None:
            # tiered layout: hot candidates detour to exact HBM scoring
            # (masked OUT of refinement), cold candidates ride the normal
            # refine path but are marked so their residual stream re-bills
            # at SSD rates via the is_delta per-level split.  With every
            # row warm both masks are all-False and each op below is an
            # identity — bit-identical to the static layout.
            hot = cand.valid & (cand.tier == TIER_HOT)
            cold = cand.valid & (cand.tier == TIER_COLD)
            rcand = cand._replace(valid=cand.valid & ~hot,
                                  d0=jnp.where(hot, jnp.inf, cand.d0),
                                  is_delta=cold, tier=None)
        with trace.span("refine", track="query", backend=self.backend.name):
            refined = self.backend.refine(chunk, rcand, self.index.trq,
                                          k=k, bound=cfg.bound, z=cfg.z)
        with trace.span("rerank", track="query", budget=budget):
            if hot is not None:
                d_hot = stages_mod._score_hot(self.index.x, chunk, cand.ids,
                                              hot)
                est = jnp.where(hot, d_hot, refined.est)
                alive = refined.alive | hot
                topk, topk_d, n_ssd, _ = stages_mod._rerank_survivors_tiered(
                    self.index.x, chunk, cand.ids, est, alive, hot,
                    k=k, budget=budget)
            else:
                topk, topk_d, n_ssd = stages_mod._rerank_survivors(
                    self.index.x, chunk, cand.ids, refined.est,
                    refined.alive, k=k, budget=budget)
        counters = dict(cand.counters)
        _accumulate(counters, refined.counters)
        _accumulate(counters, {"ssd_fetch": n_ssd})
        return topk, topk_d, counters

    def execute(self, queries: jax.Array, *, k: int | None = None,
                cost: QueryCost | None = None, pad: bool = False
                ) -> tuple[jax.Array, jax.Array, QueryCost]:
        """FaTRQ search: (Q, k) ids, (Q, k) exact squared-L2 distances,
        and the folded traffic ledger.

        ``pad=True`` pads every ragged chunk to its power-of-two bucket
        (``bucket_for``) with a per-query validity mask, so variable batch
        sizes reuse a fixed set of compiled shapes; padded rows produce no
        candidates and no counters, keeping results AND ledger
        bit-identical to the unpadded path."""
        cfg = self.index.config
        k = k or cfg.final_k
        budget = search_budget(cfg, k, self.refine_budget)
        tr = trace.active()

        with trace.span("execute", track="query", front=self.front.name,
                        backend=self.backend.name, k=k, budget=budget,
                        n_queries=int(queries.shape[0])) as sp_ex:
            topk_parts: list[jax.Array] = []
            dist_parts: list[jax.Array] = []
            counters: Counters = {}
            for chunk in self._chunks(queries):
                n = chunk.shape[0]
                if pad:
                    chunk, qvalid = pad_chunk(
                        chunk, bucket_for(n, self.micro_batch))
                else:
                    qvalid = None
                with trace.span("front", track="query",
                                stage=self.front.name, n=n):
                    cand = self.front.candidates(chunk, qvalid=qvalid)
                topk, topk_d, cnt = self._refine_rerank(
                    chunk, cand, k=k, budget=budget)
                if topk.shape[0] != n:             # drop padded rows
                    topk, topk_d = topk[:n], topk_d[:n]
                topk_parts.append(topk)
                dist_parts.append(topk_d)
                _accumulate(counters, cnt)

            cost = self._fold(counters, cost)
            if tr is not None:
                _attach_ledger(sp_ex, cost)
        return _cat(topk_parts), _cat(dist_parts), cost

    # -- staged surface (serving engine's double-buffered dispatch) -------

    def run_front(self, chunk: jax.Array, *,
                  qvalid: jax.Array | None = None):
        """Front stage only, for ONE micro-batch (no chunking): candidate
        generation is enqueued on the device and returned as a
        ``Candidates`` handle.  The serving engine issues this for batch
        N+1 while batch N's ``run_finish`` (refine + rerank) drains —
        JAX's async dispatch overlaps the two stages on device."""
        with trace.span("front", track="query", stage=self.front.name,
                        n=int(chunk.shape[0]), split=True):
            return self.front.candidates(chunk, qvalid=qvalid)

    def run_finish(self, chunk: jax.Array, cand, *, k: int | None = None,
                   cost: QueryCost | None = None
                   ) -> tuple[jax.Array, jax.Array, QueryCost]:
        """Refine + rerank + ledger fold for a ``run_front`` result.
        Together with ``run_front`` this is exactly ``execute`` on one
        chunk — same stages, same counters, same fold — so split dispatch
        stays bit-identical to the monolithic call."""
        cfg = self.index.config
        k = k or cfg.final_k
        budget = search_budget(cfg, k, self.refine_budget)
        tr = trace.active()
        with trace.span("finish", track="query", backend=self.backend.name,
                        k=k, budget=budget) as sp_fin:
            topk, topk_d, counters = self._refine_rerank(chunk, cand, k=k,
                                                         budget=budget)
            cost = self._fold(counters, cost)
            if tr is not None:
                _attach_ledger(sp_fin, cost)
        return topk, topk_d, cost

    def search(self, queries: jax.Array, *, k: int | None = None,
               cost: QueryCost | None = None) -> tuple[jax.Array, QueryCost]:
        """Legacy tuple surface: (Q, k) ids + ledger (no distances)."""
        ids, _, cost = self.execute(queries, k=k, cost=cost)
        return ids, cost

    def execute_baseline(self, queries: jax.Array, *, k: int | None = None,
                         pad: bool = False
                         ) -> tuple[jax.Array, jax.Array, QueryCost]:
        """SoTA baseline (cuVS/FAISS style): front stage, then exact rerank
        of the FULL candidate list from SSD — no far-memory refinement."""
        cfg = self.index.config
        k = k or cfg.final_k
        tr = trace.active()
        with trace.span("execute", track="query", front=self.front.name,
                        backend="baseline", k=k,
                        n_queries=int(queries.shape[0])) as sp_ex:
            topk_parts: list[jax.Array] = []
            dist_parts: list[jax.Array] = []
            counters: Counters = {}
            for chunk in self._chunks(queries):
                n = chunk.shape[0]
                if pad:
                    chunk, qvalid = pad_chunk(
                        chunk, bucket_for(n, self.micro_batch))
                else:
                    qvalid = None
                with trace.span("front", track="query",
                                stage=self.front.name, n=n):
                    cand = self.front.candidates(chunk, qvalid=qvalid)
                with trace.span("rerank", track="query", baseline=True):
                    topk, topk_d, n_valid = stages_mod._rerank_all(
                        self.index.x, chunk, cand.ids, cand.valid, k=k)
                if topk.shape[0] != n:             # drop padded rows
                    topk, topk_d = topk[:n], topk_d[:n]
                topk_parts.append(topk)
                dist_parts.append(topk_d)
                _accumulate(counters, cand.counters)
                _accumulate(counters, {"ssd_fetch": n_valid})

            counts = _collect(counters)
            cost = QueryCost()
            lay = self.index.layout
            self.front.fold_cost(cost, counts, lay)
            cost.record("rerank", Tier.SSD, counts["ssd_fetch"],
                        lay.ssd_bytes)
            cost.add_compute(_COMPUTE_S_PER_CAND * counts["front_cand"])
            if tr is not None:
                _attach_ledger(sp_ex, cost)
        return _cat(topk_parts), _cat(dist_parts), cost

    def search_baseline(self, queries: jax.Array, *, k: int | None = None
                        ) -> tuple[jax.Array, QueryCost]:
        """Legacy tuple surface over ``execute_baseline``."""
        ids, _, cost = self.execute_baseline(queries, k=k)
        return ids, cost

    # -- cost folding -----------------------------------------------------

    def _fold(self, counters: Counters, cost: QueryCost | None) -> QueryCost:
        """One host transfer: device counters → Table-I traffic ledger.
        The tiered layout's per-list access histogram rides the same
        transfer and feeds the index's heat tracker here — heat tracking
        costs no extra device round-trips."""
        with trace.span("fold", track="query"):
            counts = _collect(counters)
            heat = counts.pop("list_heat", None)
            if heat is not None:
                observe = getattr(self.index, "observe_heat", None)
                if observe is not None:
                    observe(heat)
            if trace.active() is not None:
                self._level_events(counts)
            return fold_counts(counts, cost=cost, config=self.index.config,
                               layout=self.index.layout,
                               front_fold=self.front.fold_cost)

    def _level_events(self, counts: dict) -> None:
        """One ``refine.l{ℓ}`` event per TRQ level with the candidates
        entering it and their delta-page share, from the counters the
        fold already transferred: the per-level view (the paper's early
        exit) that the folded ledger flattens away.  Level 0 streams
        every candidate, level ℓ ≥ 1 only survivors, as in
        ``fold_counts``."""
        n_alive = counts.get("refine_alive", 0)
        for lv in range(self.index.config.trq_levels):
            if lv == 0:
                n_lv = counts.get("front_cand", 0)
                n_lv_delta = counts.get("delta_cand", 0)
            else:
                n_lv = counts.get(f"refine_alive_l{lv}", n_alive)
                n_lv_delta = counts.get(f"refine_alive_l{lv}_delta", 0)
            trace.event(f"refine.l{lv}", track="query", level=lv,
                        entering=int(n_lv), delta=int(n_lv_delta))


def _attach_ledger(handle, cost: QueryCost) -> None:
    """Attach the folded Table-I ledger + modeled breakdown to a span.

    Note the ledger reflects the ``cost`` object AFTER the fold — when a
    caller threads a running ``cost=`` across calls (serving batch
    totals) the attrs carry the cumulative state, matching what the
    caller receives."""
    handle.set_attrs(
        ledger={key: [t.accesses, t.bytes]
                for key, t in sorted(cost.ledger.items())},
        model_breakdown_s=cost.breakdown(),
        model_total_s=cost.total_seconds())


def fold_counts(counts: dict[str, int], *, cost: QueryCost | None, config,
                layout, front_fold) -> QueryCost:
    """Fold collected stage counters into a Table-I traffic ledger.

    Shared between the unsharded ``SearchExecutor`` and the per-shard fold
    in ``anns.sharding`` (which builds one ledger per shard from the same
    counter names, then combines them with ``QueryCost.merge_parallel``).
    """
    cost = cost or QueryCost()
    n_cand = counts["front_cand"]
    n_alive = counts["refine_alive"]
    # tiered layout (anns.tiered): hot candidates score exactly against
    # HBM-resident full vectors and never touch far memory; cold
    # candidates' residual stream re-bills at SSD rates.  The tiered
    # front ALWAYS emits both counters (zero-valued when all-warm), and
    # no other front emits them — "tiered" and "streaming" marking are
    # mutually exclusive, so the per-level marked share below is
    # unambiguous.
    tiered = "cold_cand" in counts
    n_hot = counts.get("hot_cand", 0)
    n_cold = counts.get("cold_cand", 0)

    front_fold(cost, counts, layout)
    # front → refine handoff: 4 B coarse distance per candidate (§IV);
    # hot candidates stay on device, so nothing crosses for them
    cost.record("handoff", Tier.CXL, n_cand - n_hot, 4)
    if n_hot:
        cost.record("hot", Tier.HBM, n_hot, layout.ssd_bytes)
    # level-0 codes stream from far memory for ALL candidates; level
    # ℓ ≥ 1 only for survivors of level ℓ−1.  The backends emit the
    # actual per-level entering counts (``refine_alive_l{ℓ}``); the
    # final-survivor count is only a fallback for legacy counter dicts
    # that predate per-level counters (it UNDER-charges levels 1..L−1,
    # since the mask chain is monotonically shrinking).
    # Candidates that came off delta pages (streaming subsystem, counter
    # ``delta_cand``) stream the SAME far-memory bytes but are billed to a
    # DISTINCT ledger entry so delta-list traffic stays visible; static
    # indexes never emit the counters and their ledgers are unchanged.
    # The split covers EVERY level of the stream: level 0 via
    # ``delta_cand`` (all candidates), levels ℓ ≥ 1 via the per-level
    # delta survivor counters (``refine_alive_l{ℓ}_delta``) both refine
    # backends emit whenever the front marks delta candidates.
    # On the tiered layout the refine backends see cold candidates via the
    # SAME is_delta marking mechanism, so ``refine_alive_l{ℓ}_delta`` is
    # the cold-entering share there and re-bills to ``cold:ssd``.
    n_delta = counts.get("delta_cand", 0)
    cost.record("refine", Tier.CXL, n_cand - n_delta - n_hot - n_cold,
                layout.far_bytes)
    if n_delta:
        cost.record("delta", Tier.CXL, n_delta, layout.far_bytes)
    if n_cold:
        cost.record("cold", Tier.SSD, n_cold, layout.far_bytes)
    for lv in range(1, config.trq_levels):
        n_lv = counts.get(f"refine_alive_l{lv}", n_alive)
        n_lv_mark = counts.get(f"refine_alive_l{lv}_delta", 0)
        cost.record("refine", Tier.CXL, n_lv - n_lv_mark, layout.far_bytes)
        if n_lv_mark:
            if tiered:
                cost.record("cold", Tier.SSD, n_lv_mark, layout.far_bytes)
            else:
                cost.record("delta", Tier.CXL, n_lv_mark, layout.far_bytes)
    # survivors (≤ budget per query) hit SSD
    cost.record("rerank", Tier.SSD, counts["ssd_fetch"], layout.ssd_bytes)
    cost.add_compute(_COMPUTE_S_PER_CAND * n_cand)
    return cost


# -------------------------------------------------------- executor caching
# Caches live ON the index instance (plain attributes), so their lifetime is
# exactly the index's lifetime — the resulting index↔executor reference
# cycle is ordinary gc fodder, with no process-global registry to leak.
# (The kNN-graph cache moved to ``stages.graph_for`` with the front
# factories; ``_graph_for`` stays importable from here.)


def make_executor(index, *, front: str = "ivf", backend: str = "reference",
                  micro_batch: int | None = None,
                  refine_budget: int | None = None, layout: str = "static",
                  **front_opts) -> SearchExecutor:
    """Memoized executor factory — facade entry point.

    Executors are cached per (generation, front, backend, micro_batch,
    refine_budget, layout) so the compatibility wrappers in
    ``anns.pipeline`` and the serving layer can call this on every request
    without rebuilding stages.  The generation component makes migration
    visible: after a ``TieredIndex.rebalance_tiers()`` the old executors'
    front stages hold superseded placement arrays, so stale-generation
    entries are pruned and a fresh executor is built (static indexes have
    no generation and keep the behavior they always had).
    """
    gen = getattr(index, "generation", 0)
    key = (gen, front, backend, micro_batch, refine_budget, layout,
           tuple(sorted(front_opts.items())))
    cache = getattr(index, "_executor_cache", None)
    if cache is None:
        cache = {}
        index._executor_cache = cache
    ex = cache.get(key)
    if ex is None:
        ex = SearchExecutor.from_index(index, front=front, backend=backend,
                                       micro_batch=micro_batch,
                                       refine_budget=refine_budget,
                                       layout=layout, **front_opts)
        for kk in [kk for kk in cache if kk[0] != gen]:
            del cache[kk]
        cache[key] = ex
    return ex
