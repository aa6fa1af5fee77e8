"""Sharded search subsystem: mesh-partitioned database + shard_map datapath.

Scale-out of the staged executor across devices (paper Fig. 6 scales
throughput by replicating the refinement datapath across far-memory
channels; COSMOS/HAVEN reach billion-scale by partitioning the candidate
datapath).  The partitioner and the in-shard front are LAYOUT-PLUGGABLE:
each front registers ``registry.ShardedFrontHooks`` — a partition scheme,
a shard_map front body, and a ledger fold — and everything downstream of
candidate generation (refine, rerank, merge, cost fold) is shared.

* ``partition_database(index, S, front=...)`` — dispatches to the front's
  partitioner:

  - **IVF** assigns WHOLE inverted lists to shards (a candidate's codes,
    scalars and full vector co-reside with its list), balanced by list
    length with an LPT greedy (heaviest list onto the lightest shard).
  - **graph** partitions the VECTORS into contiguous row ranges and gives
    each shard its subgraph plus HALO state: the adjacency of its owned
    rows (global ids and local slots) and the PQ-reconstruction vectors of
    every off-shard boundary neighbor, so a shard can expand any node it
    owns without touching another shard's memory mid-hop.

  Per-shard record arrays are stacked on a leading shard axis and row ids
  are re-indexed shard-locally; ``gid`` maps local rows back to global
  database ids.

* ``ShardedIndex`` — the stacked database placed on a 1-D ``("search",)``
  mesh: every per-record array (and the front's ``front_db``) sharded on
  its leading axis; the PQ codebook, calibration model and the front's
  ``front_rep`` pytree (IVF: the coarse centroids) replicated.

* ``ShardedExecutor`` — runs front → refine → rerank per shard under
  ``jax.shard_map`` (queries replicated, database sharded).
  Equivalence with the unsharded ``SearchExecutor`` is exact, not
  approximate, because every data-dependent decision is globalized:

    - IVF front: each shard ranks the REPLICATED centroid table and
      selects the global top-``nprobe`` lists, keeping only the ones it
      owns — the union across shards is exactly the unsharded probe set;
    - graph front: the beam state (global ids, distances, expanded flags)
      is REPLICATED across shards and advances in lockstep; each hop, the
      owner of every picked node contributes its adjacency and the
      locally-computed neighbor distances (from its halo copy of the PQ
      reconstructions) to a ``psum`` frontier exchange — zeros elsewhere,
      so the summed lists are bit-exact — and the shared
      ``graph.beam_merge`` applies the exact dedup/tie-breaking the
      single-device search uses;
    - refine: pruning thresholds pool each shard's k smallest upper bounds
      with an all-gather, so the global kth smallest (and hence every
      survivor mask) matches the unsharded run bit-for-bit;
    - rerank: the SSD budget is enforced globally the same way (budget-th
      smallest estimate across shards), each shard fetches only its own
      survivors, and a final ``lax.top_k`` over all-gathered
      (distance, global id) pairs merges shard-local top-k (exact up to
      exact-f32-estimate ties at the budget boundary — see
      ``_rerank_survivors_sharded``).

  Stage counters stay device-side per shard; one host transfer at the end
  builds one ``QueryCost`` ledger PER SHARD, folded with
  ``QueryCost.merge_parallel`` (shards run concurrently: per-tier time is
  the max across shard ledgers, bytes/accesses sum).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.anns import registry
from repro.anns.executor import (_accumulate, _attach_ledger, _cat,
                                 bucket_for, fold_counts, iter_chunks,
                                 pad_chunk, search_budget)
from repro.anns.stages import (Candidates, Counters, adc_score,
                               fold_graph_front_cost, fold_ivf_front_cost,
                               graph_for, rank_centroid_lists)
from repro.core.decomposition import RecordScalars
from repro.core.estimator import pooled_k_smallest
from repro.core.trq import TRQCodes, TRQLevel
from repro.index import graph as graph_mod
from repro.memory import QueryCost, RecordLayout
from repro.obs import trace
from repro.quant import pq as pq_mod

AXIS = "search"


# ------------------------------------------------------------- partitioner


def _stack_rows(arr, rows_per_shard: list[np.ndarray], n_max: int):
    """Gather per-shard row subsets of a global (N, ...) array and stack
    them on a leading shard axis, zero-padding ragged shards to n_max."""
    a = np.asarray(arr)
    out = np.zeros((len(rows_per_shard), n_max) + a.shape[1:], a.dtype)
    for s, rows in enumerate(rows_per_shard):
        out[s, :rows.size] = a[rows]
    return jnp.asarray(out)


@dataclass(eq=False)
class ShardedIndex:
    """A FaTRQIndex partitioned into S shards, stacked on a leading axis.

    Replicated: ``codebook`` (PQ), the calibration model inside ``trq``,
    and the front's ``front_rep`` pytree (IVF: the coarse centroid table;
    graph: empty — its traversal state is the replicated beam itself).
    Sharded (leading axis S): the front's ``front_db`` pytree (IVF:
    inverted lists with LOCAL row ids; graph: subgraph adjacency + halo
    vectors + the global→local owner map), per-record
    ``pq_codes``/``trq``/``x``, and ``gid`` (local row → global id).
    ``front_args`` is the hashable tuple of static traversal parameters
    captured at partition time.
    """

    config: "PipelineConfig"         # noqa: F821 - import cycle via pipeline
    layout: RecordLayout
    n_shards: int
    front: str                       # which front this partition serves
    codebook: pq_mod.PQCodebook      # replicated
    front_rep: tuple                 # replicated front pytree
    front_db: tuple                  # sharded front pytree (leading S axis)
    front_args: tuple                # static (name, value) traversal args
    pq_codes: jax.Array              # (S, n_max, M) uint8
    trq: TRQCodes                    # every per-record leaf (S, n_max, ...)
    x: jax.Array                     # (S, n_max, D) full precision ("SSD")
    gid: jax.Array                   # (S, n_max) global row id, -1 pad
    shard_rows: np.ndarray           # (S,) host-side real row counts
    mesh: jax.sharding.Mesh | None = None

    # back-compat views of the IVF front's pytrees (pre-refactor fields)
    @property
    def centroids(self) -> jax.Array:
        return self.front_rep[0]

    @property
    def list_gid(self) -> jax.Array:
        return self.front_db[0]

    @property
    def lists(self) -> jax.Array:
        return self.front_db[1]

    def place(self, mesh) -> "ShardedIndex":
        """Place the index on a 1-D ``("search",)`` mesh: per-record arrays
        and the front_db sharded on the leading shard axis, globals
        replicated."""
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if sizes.get(AXIS) != self.n_shards:
            raise ValueError(f"mesh axis {AXIS!r} has size {sizes.get(AXIS)} "
                             f"but the index has {self.n_shards} shards")
        shard = NamedSharding(mesh, P(AXIS))
        rep = NamedSharding(mesh, P())
        put_s = lambda a: jax.device_put(a, shard)            # noqa: E731
        put_r = lambda a: jax.device_put(a, rep)              # noqa: E731
        trq = TRQCodes(
            dim=self.trq.dim,
            levels=jax.tree.map(put_s, self.trq.levels),
            scalars=jax.tree.map(put_s, self.trq.scalars),
            model=jax.tree.map(put_r, self.trq.model))
        return dataclasses.replace(
            self, mesh=mesh,
            codebook=jax.tree.map(put_r, self.codebook),
            front_rep=jax.tree.map(put_r, self.front_rep),
            front_db=jax.tree.map(put_s, self.front_db),
            pq_codes=put_s(self.pq_codes), trq=trq,
            x=put_s(self.x), gid=put_s(self.gid))


def lpt_assign(lens: np.ndarray, n_shards: int
               ) -> tuple[list[list[int]], np.ndarray]:
    """LPT greedy list→shard assignment: sort lists by member count
    descending, place each on the currently lightest shard.  Bounds the
    heaviest shard at (4/3 − 1/3S)× the optimum.  Returns (per-shard list
    ids, per-shard loads).  Shared by ``partition_database`` and the
    streaming subsystem's drift metric / ``rebalance()``
    (anns/streaming.py), so the rebalance trigger tests the exact bound
    the partitioner guarantees.
    """
    order = np.argsort(-lens, kind="stable")
    loads = np.zeros(n_shards, np.int64)
    members: list[list[int]] = [[] for _ in range(n_shards)]
    for li in order:
        s = int(np.argmin(loads))
        members[s].append(int(li))
        loads[s] += int(lens[li])
    return members, loads


def _partition_ivf_front(index, n_shards: int):
    """IVF partitioner: whole inverted lists → shards via ``lpt_assign``.
    Returns (per-shard global rows, replicated pytree, shard-stacked front
    pytree, static front args)."""
    ivf = index.ivf
    lens = np.asarray(ivf.list_len)
    lists_np = np.asarray(ivf.lists)
    nlist, cap = lists_np.shape
    if not 1 <= n_shards <= nlist:
        raise ValueError(f"n_shards={n_shards} must be in [1, nlist={nlist}]"
                         f" — whole lists are the partitioning unit")

    members, _ = lpt_assign(lens, n_shards)

    lmax = max(len(m) for m in members)
    rows_per: list[np.ndarray] = []
    list_gid = np.full((n_shards, lmax), -1, np.int32)
    local_lists = np.full((n_shards, lmax, cap), -1, np.int32)
    for s, m in enumerate(members):
        off = 0
        rows: list[np.ndarray] = []
        for j, li in enumerate(m):
            n_li = int(lens[li])
            list_gid[s, j] = li
            local_lists[s, j, :n_li] = np.arange(off, off + n_li)
            rows.append(lists_np[li, :n_li])
            off += n_li
        rows_per.append(np.concatenate(rows) if rows
                        else np.zeros((0,), np.int32))
    rep = (ivf.centroids,)
    fdb = (jnp.asarray(list_gid), jnp.asarray(local_lists))
    return rows_per, rep, fdb, (("nprobe", index.config.nprobe),)


def _partition_graph_front(index, n_shards: int):
    """Graph partitioner: contiguous vector ranges → shards, each with its
    subgraph + halo.

    Per shard: the adjacency of its owned rows both as GLOBAL ids (what the
    frontier exchange publishes) and as LOCAL slots into ``xs_loc`` — the
    shard's copy of the PQ reconstructions for its owned rows FOLLOWED BY
    every off-shard boundary neighbor (the halo).  ``loc_of`` maps global
    row → owned local row (-1 off-shard): it decides frontier-exchange
    ownership and maps the final beam onto the shard's record store.
    ``xs_loc`` is gathered from one globally-decoded array so halo copies
    are bit-identical to the owner's values.
    """
    n = int(index.x.shape[0])
    if not 1 <= n_shards <= n:
        raise ValueError(f"n_shards={n_shards} must be in [1, n={n}] — "
                         f"vectors are the partitioning unit")
    g = np.asarray(graph_for(index).neighbors)
    degree = g.shape[1]
    x_score = np.asarray(pq_mod.decode(index.codebook, index.pq_codes))
    rows_per = [r.astype(np.int32)
                for r in np.array_split(np.arange(n), n_shards)]
    ns_max = max(r.size for r in rows_per)

    loc_of = np.full((n_shards, n), -1, np.int32)
    halos: list[np.ndarray] = []
    for s, rows in enumerate(rows_per):
        loc_of[s, rows] = np.arange(rows.size, dtype=np.int32)
        nbr = g[rows]
        halos.append(np.unique(nbr[loc_of[s, nbr] < 0]))
    nloc_max = max(1, max(r.size + h.size for r, h in zip(rows_per, halos)))

    xs_loc = np.zeros((n_shards, nloc_max, x_score.shape[1]), np.float32)
    adj_gid = np.zeros((n_shards, ns_max, degree), np.int32)
    adj_loc = np.zeros((n_shards, ns_max, degree), np.int32)
    for s, (rows, halo) in enumerate(zip(rows_per, halos)):
        local = np.concatenate([rows, halo])
        xs_loc[s, :local.size] = x_score[local]
        full_loc = loc_of[s].copy()
        full_loc[halo] = rows.size + np.arange(halo.size, dtype=np.int32)
        adj_gid[s, :rows.size] = g[rows]
        adj_loc[s, :rows.size] = full_loc[g[rows]]

    fdb = (jnp.asarray(xs_loc), jnp.asarray(adj_gid),
           jnp.asarray(adj_loc), jnp.asarray(loc_of))
    # static traversal args — MUST match GraphFrontStage's defaults, the
    # single-shard baseline the equivalence tests pin against
    args = (("beam", 64), ("iters", 32), ("expand", 4), ("n", n),
            ("degree", degree))
    return rows_per, (), fdb, args


def partition_database(index, n_shards: int,
                       front: str = "ivf") -> ShardedIndex:
    """Partition ``index`` for ``front``'s sharded datapath.

    The front's registered hooks choose the scheme (whole IVF lists vs
    vector ranges + halo); the per-record arrays (PQ codes, TRQ levels +
    scalars, full vectors) are then gathered into shard-local row order the
    same way for every front, so the refine/rerank datapath indexes them
    densely regardless of how candidates were generated.
    """
    hooks = registry.sharded_front(front)
    rows_per, front_rep, front_db, front_args = hooks.partition(
        index, n_shards)
    shard_rows = np.array([r.size for r in rows_per])
    n_max = max(int(shard_rows.max()), 1)

    gid = np.full((n_shards, n_max), -1, np.int32)
    for s, rows in enumerate(rows_per):
        gid[s, :rows.size] = rows

    trq = index.trq
    levels = tuple(
        TRQLevel(packed=_stack_rows(lv.packed, rows_per, n_max),
                 proj=_stack_rows(lv.proj, rows_per, n_max),
                 norm=_stack_rows(lv.norm, rows_per, n_max),
                 rho=_stack_rows(lv.rho, rows_per, n_max))
        for lv in trq.levels)
    scalars = RecordScalars(
        delta_sq=_stack_rows(trq.scalars.delta_sq, rows_per, n_max),
        cross=_stack_rows(trq.scalars.cross, rows_per, n_max),
        rho=_stack_rows(trq.scalars.rho, rows_per, n_max),
        norm=_stack_rows(trq.scalars.norm, rows_per, n_max))

    return ShardedIndex(
        config=index.config, layout=index.layout, n_shards=n_shards,
        front=front, codebook=index.codebook,
        front_rep=front_rep, front_db=front_db, front_args=front_args,
        pq_codes=_stack_rows(index.pq_codes, rows_per, n_max),
        trq=TRQCodes(dim=trq.dim, levels=levels, scalars=scalars,
                     model=trq.model),
        x=_stack_rows(index.x, rows_per, n_max),
        gid=jnp.asarray(gid), shard_rows=shard_rows)


# ------------------------------------------------------ per-shard fronts


def _ivf_shard_front(queries, rep, fdb, codebook, pq_codes, *,
                     qvalid=None, nprobe: int) -> Candidates:
    """IVF front inside the shard_map body: rank the replicated centroid
    table globally, gather only the chosen lists this shard owns.
    ``qvalid`` (replicated (Q,) mask) zeroes padded query rows out of the
    candidate set and the counters — see ``stages.FrontStage``."""
    (centroids,) = rep
    list_gid, lists = fdb
    nq = queries.shape[0]
    lmax, cap = lists.shape

    d_cent, top_lists = rank_centroid_lists(centroids, queries,
                                            nprobe=nprobe)
    chosen = jnp.any(list_gid[None, :, None] == top_lists[:, None, :],
                     axis=-1)                                 # (Q, Lmax)
    # Gather only the chosen owned lists — the global top-nprobe set has
    # nprobe lists TOTAL across shards, so ≤ nprobe local slots always
    # suffice; scoring the whole shard would cost Lmax/nprobe× more.
    pl = min(nprobe, lmax)
    d_own = jnp.where(chosen & (list_gid >= 0)[None, :],
                      d_cent[:, jnp.maximum(list_gid, 0)], jnp.inf)
    _, slot = jax.lax.top_k(-d_own, pl)                       # (Q, pl)
    sel = jnp.take_along_axis(chosen, slot, axis=1)           # (Q, pl)
    ids_l = lists[slot]                                       # (Q, pl, cap)
    valid = ((ids_l >= 0) & sel[:, :, None]).reshape(nq, pl * cap)
    if qvalid is not None:
        valid = valid & qvalid[:, None]
    ids = jnp.maximum(ids_l.reshape(nq, pl * cap), 0)
    d0 = adc_score(codebook, pq_codes, ids, queries, valid)
    return Candidates(ids=ids, valid=valid, d0=d0,
                      counters={"front_cand": jnp.sum(valid)})


def _graph_shard_front(queries, rep, fdb, codebook, pq_codes, *,
                       qvalid=None, beam: int, iters: int, expand: int,
                       n: int, degree: int) -> Candidates:
    """Graph front inside the shard_map body: replicated beam, per-hop
    frontier exchange over the halo-partitioned subgraphs.

    The beam state (global ids, distances, expanded flags) is identical on
    every shard and advances in lockstep.  Each hop, the shared
    ``graph.pick_frontier`` selects the same picks everywhere; the OWNER of
    each picked node contributes its adjacency row (global ids) and the
    neighbor distances computed from its local ``xs_loc`` copy, everyone
    else contributes zeros, and one ``psum`` per tensor reassembles the
    exact flattened neighbor list the single-device search builds (x + 0
    is exact for finite f32, and each node has exactly one owner).  The
    shared ``graph.beam_merge`` then applies the identical dedup /
    tie-breaking, so the final beam is bit-identical to the unsharded
    ``GraphFrontStage`` — each shard claims the slots it owns and
    ADC-scores only those against its local record store.
    """
    xs_loc, adj_gid, adj_loc, loc_of = fdb
    nq = queries.shape[0]
    start = jax.random.randint(jax.random.PRNGKey(0), (beam,), 0, n)

    def owner_dist(gids):
        """(Q, ...) global ids → (owned?, psum'd exact distances)."""
        lrow = loc_of[gids]
        own = lrow >= 0
        dloc = jnp.sum(
            (xs_loc[jnp.maximum(lrow, 0)] - queries.reshape(
                (nq,) + (1,) * (gids.ndim - 1) + (-1,))) ** 2, axis=-1)
        return own, jax.lax.psum(jnp.where(own, dloc, 0.0), AXIS)

    ids0 = jnp.broadcast_to(start[None], (nq, beam))
    _, ds0 = owner_dist(ids0)
    exp0 = jnp.zeros((nq, beam), bool)

    def body(carry, _):
        ids, ds, expanded, hops = carry
        picks, expanded = jax.vmap(
            partial(graph_mod.pick_frontier, expand=expand))(ds, expanded)
        pg = jnp.take_along_axis(ids, picks, axis=1)          # (Q, E)
        pl = loc_of[pg]
        own = pl >= 0
        pls = jnp.maximum(pl, 0)
        neigh = jax.lax.psum(
            jnp.where(own[..., None], adj_gid[pls], 0), AXIS)
        # neighbor distances come from the owner's adjacency-LOCAL slots
        # (its xs_loc covers owned rows + halo, so every edge resolves)
        nd = jnp.sum((xs_loc[adj_loc[pls]]
                      - queries[:, None, None, :]) ** 2, axis=-1)
        nd = jax.lax.psum(jnp.where(own[..., None], nd, 0.0), AXIS)
        hop_own = own if qvalid is None else own & qvalid[:, None]
        hops = hops + jnp.sum(hop_own.astype(jnp.int32))
        ids, ds, expanded = jax.vmap(
            partial(graph_mod.beam_merge, beam=beam))(
            ids, ds, expanded, neigh.reshape(nq, -1), nd.reshape(nq, -1))
        return (ids, ds, expanded, hops), None

    (ids, ds, _, hops), _ = jax.lax.scan(
        body, (ids0, ds0, exp0, jnp.asarray(0, jnp.int32)), None,
        length=iters)
    order = jnp.argsort(ds, axis=1)
    beam_ids = jnp.take_along_axis(ids, order, axis=1)        # (Q, beam)

    lfin = loc_of[beam_ids]
    valid = lfin >= 0                                         # owned slots
    if qvalid is not None:
        valid = valid & qvalid[:, None]
    ids_local = jnp.maximum(lfin, 0)
    d0 = adc_score(codebook, pq_codes, ids_local, queries, valid)
    return Candidates(ids=ids_local, valid=valid, d0=d0,
                      counters={"front_cand": jnp.sum(valid),
                                "front_hops": hops * degree})


registry.register_sharded_front("ivf", registry.ShardedFrontHooks(
    partition=_partition_ivf_front, body=_ivf_shard_front,
    fold=fold_ivf_front_cost))
registry.register_sharded_front("graph", registry.ShardedFrontHooks(
    partition=_partition_graph_front, body=_graph_shard_front,
    fold=fold_graph_front_cost))


# ------------------------------------------------------ per-shard datapath


def _rerank_survivors_sharded(x, gid, queries, ids, est, alive, *, k: int,
                              budget: int, axis_name: str):
    """Shard-local exact rerank under a GLOBAL SSD budget.

    The fetch set must match the unsharded executor's exactly: take each
    shard's ``min(budget, C_s)`` best estimates, pool them with an
    all-gather to find the global budget-th smallest estimate among alive
    candidates, and fetch only local survivors at or below it.  Returns
    (exact distances, global ids, local fetch count) — distances are +inf
    outside the fetch set so the cross-shard top-k merge ignores them.

    Tie caveat: the unsharded path cuts EXACTLY ``budget`` slots with
    ``top_k`` (index-order tie-break), while this threshold cut keeps every
    candidate at ``tau_b``; records with exactly equal f32 estimates
    straddling the budget boundary (e.g. duplicate database rows) can
    therefore fetch one extra candidate per tie.  Real-valued data makes
    such exact ties measure-zero, and the two paths' candidate orderings
    differ anyway, so index-order tie-breaking is not reproducible across
    them in either direction.
    """
    bl = min(budget, est.shape[1])
    est_m = jnp.where(alive, est, jnp.inf)
    neg_local, order = jax.lax.top_k(-est_m, bl)              # (Q, bl)
    tau_b = pooled_k_smallest(est_m, budget, axis_name)       # (Q,)

    fetch_ids = jnp.take_along_axis(ids, order, axis=1)
    fetch_alive = jnp.take_along_axis(alive, order, axis=1) & \
        (-neg_local <= tau_b[:, None])
    d = jnp.sum((x[fetch_ids] - queries[:, None, :]) ** 2, axis=-1)
    d = jnp.where(fetch_alive, d, jnp.inf)
    fetch_gid = gid[fetch_ids]                                # (Q, bl)
    return d, fetch_gid, jnp.sum(fetch_alive)


def _shard_body(queries, qvalid, front_rep, codebook, model, front_db,
                rec_db, *, dim: int, k: int, budget: int, bound: str,
                z: float, backend: str, front: str, front_args: tuple):
    """One shard's front → refine → rerank, with globalized decisions.

    Runs under shard_map: ``queries``/``qvalid``/``front_rep``/
    ``codebook``/``model`` are replicated; ``front_db``/``rec_db`` leaves
    carry a leading length-1 shard-block dim.  The front's candidate
    generation comes from its registered ``ShardedFrontHooks.body``
    (``qvalid`` masks padded query rows out of candidates and counters on
    every shard identically); refine, rerank and the cross-shard merge
    are front-agnostic.
    """
    front_local = jax.tree.map(lambda a: a[0], front_db)
    pq_codes, levels, scalars, x, gid = jax.tree.map(
        lambda a: a[0], rec_db)
    trq = TRQCodes(dim=dim, levels=levels, scalars=scalars, model=model)

    # -- front: the registered per-shard body (may use mesh collectives) --
    cand = registry.sharded_front(front).body(
        queries, front_rep, front_local, codebook, pq_codes, qvalid=qvalid,
        **dict(front_args))

    # -- refine: registered backends, thresholds pooled across the axis ---
    be = registry.make_backend(backend)
    refined = be.refine(queries, cand, trq, k=k, bound=bound, z=z,
                        axis_name=AXIS)

    # -- rerank + cross-shard top-k merge ---------------------------------
    d, fetch_gid, n_ssd = _rerank_survivors_sharded(
        x, gid, queries, cand.ids, refined.est, refined.alive,
        k=k, budget=budget, axis_name=AXIS)
    d_all = jax.lax.all_gather(d, AXIS, axis=1, tiled=True)
    g_all = jax.lax.all_gather(fetch_gid, AXIS, axis=1, tiled=True)
    neg_d, best = jax.lax.top_k(-d_all, k)
    topk = jnp.take_along_axis(g_all, best, axis=1)           # replicated
    topk_d = -neg_d                                           # replicated

    counters = dict(cand.counters)
    counters.update(refined.counters)
    counters["ssd_fetch"] = n_ssd
    counters = {n: v.reshape(1).astype(jnp.int32)
                for n, v in counters.items()}                 # (1,) → (S,)
    return topk, topk_d, counters


@partial(jax.jit, static_argnames=("mesh", "dim", "k", "budget", "bound",
                                   "z", "backend", "front", "front_args"))
def _sharded_search(mesh, queries, qvalid, front_rep, codebook, trq_model,
                    front_db, rec_db, *, dim: int, k: int, budget: int,
                    bound: str, z: float, backend: str, front: str,
                    front_args: tuple):
    body = partial(_shard_body, dim=dim, k=k, budget=budget, bound=bound,
                   z=z, backend=backend, front=front, front_args=front_args)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), P(), P(), P(), P(), P(AXIS), P(AXIS)),
                       out_specs=(P(), P(), P(AXIS)),
                       check_vma=False)
    return fn(queries, qvalid, front_rep, codebook, trq_model, front_db,
              rec_db)


# ---------------------------------------------------------------- executor


@dataclass
class ShardedExecutor:
    """Mesh-parallel staged search over a ShardedIndex.

    Bit-identical top-k to the unsharded ``SearchExecutor`` on the same
    database for BOTH fronts (see module docstring for why), with
    per-shard QueryCost ledgers folded under the parallel-shard overlap
    model.
    """

    sharded: ShardedIndex
    backend: str = "reference"
    micro_batch: int | None = None
    refine_budget: int | None = None  # plan-level SSD budget override

    def __post_init__(self):
        registry.backend_spec(self.backend)   # PlanError on unknown names

    # -- construction -----------------------------------------------------

    @classmethod
    def from_index(cls, index, *, shards: int, front: str = "ivf",
                   backend: str = "reference", mesh=None,
                   micro_batch: int | None = None,
                   refine_budget: int | None = None) -> "ShardedExecutor":
        """Partition ``index`` into ``shards`` for ``front`` and place it
        on ``mesh`` (default: a fresh ``("search",)`` mesh over the first
        S devices)."""
        if mesh is None:
            from repro.launch.mesh import make_search_mesh
            mesh = make_search_mesh(shards)
        si = partition_database(index, shards, front=front).place(mesh)
        return cls(sharded=si, backend=backend, micro_batch=micro_batch,
                   refine_budget=refine_budget)

    # -- search -----------------------------------------------------------

    def execute(self, queries: jax.Array, *, k: int | None = None,
                cost: QueryCost | None = None, pad: bool = False
                ) -> tuple[jax.Array, jax.Array, QueryCost]:
        """Sharded FaTRQ search: (Q, k) GLOBAL ids, (Q, k) exact squared-L2
        distances, and the merged per-shard ledger.  ``pad=True`` pads
        ragged chunks to their power-of-two bucket (replicated validity
        mask), exactly like ``SearchExecutor.execute``."""
        si = self.sharded
        cfg = si.config
        k = k or cfg.final_k
        budget = search_budget(cfg, k, self.refine_budget)
        rec_db = (si.pq_codes, si.trq.levels, si.trq.scalars, si.x, si.gid)
        tr = trace.active()

        with trace.span("execute", track="query", front=si.front,
                        backend=self.backend, k=k, budget=budget,
                        shards=si.n_shards, fused=True,
                        n_queries=int(queries.shape[0])) as sp_ex:
            topk_parts: list[jax.Array] = []
            dist_parts: list[jax.Array] = []
            counters: Counters = {}
            for chunk in iter_chunks(queries, self.micro_batch):
                n = chunk.shape[0]
                if pad:
                    chunk, qvalid = pad_chunk(
                        chunk, bucket_for(n, self.micro_batch))
                else:
                    qvalid = jnp.ones((n,), bool)
                topk, topk_d, cnt = _sharded_search(
                    si.mesh, chunk, qvalid, si.front_rep, si.codebook,
                    si.trq.model, si.front_db, rec_db, dim=si.trq.dim, k=k,
                    budget=budget, bound=cfg.bound, z=cfg.z,
                    backend=self.backend, front=si.front,
                    front_args=si.front_args)
                if topk.shape[0] != n:             # drop padded rows
                    topk, topk_d = topk[:n], topk_d[:n]
                topk_parts.append(topk)
                dist_parts.append(topk_d)
                _accumulate(counters, cnt)

            merged = self._fold(counters)
            if tr is not None:
                # the shard_map body fuses front/refine/rerank into one
                # compiled region — no host-side stage boundaries exist, so
                # emit one event per stage instead (fused=True) to keep the
                # span↔ledger coverage invariant on the sharded layout.
                sid = sp_ex.span.sid
                for stage in ("front", "refine", "rerank"):
                    tr.event(stage, track="query", parent=sid, fused=True)
                _attach_ledger(sp_ex, merged)
            if cost is not None:
                merged = cost.merge(merged)
        return _cat(topk_parts), _cat(dist_parts), merged

    def search(self, queries: jax.Array, *, k: int | None = None,
               cost: QueryCost | None = None) -> tuple[jax.Array, QueryCost]:
        """Legacy tuple surface: (Q, k) GLOBAL ids + the merged ledger."""
        ids, _, merged = self.execute(queries, k=k, cost=cost)
        return ids, merged

    # -- cost folding -----------------------------------------------------

    def _fold(self, counters: Counters) -> QueryCost:
        """One host transfer: (S,)-stacked shard counters → S Table-I
        ledgers → one parallel-folded QueryCost (max time, summed bytes).
        The front's registered fold keeps per-front traffic models (IVF
        coarse probe vs graph hop stream) consistent with the unsharded
        stages."""
        si = self.sharded
        front_fold = registry.sharded_front(si.front).fold
        names = list(counters)
        with trace.span("wait", track="query"):
            vals = jax.device_get([counters[n] for n in names])

        shard_costs = []
        for s in range(si.n_shards):
            counts = {n: int(v[s]) for n, v in zip(names, vals)}
            shard_costs.append(fold_counts(
                counts, cost=None, config=si.config, layout=si.layout,
                front_fold=front_fold))
        merged = shard_costs[0]
        for c in shard_costs[1:]:
            merged.merge_parallel(c)
        return merged


def make_sharded_executor(index, *, shards: int, front: str = "ivf",
                          backend: str = "reference",
                          micro_batch: int | None = None,
                          refine_budget: int | None = None, mesh=None
                          ) -> ShardedExecutor:
    """Memoized sharded-executor factory (facade entry point).

    Partitioning + placement run once per (index, shards, front);
    executors are additionally cached per (backend, micro_batch,
    refine_budget) so ``anns.pipeline`` and ``serving`` can call this on
    every request.
    """
    key = (shards, front, backend, micro_batch, refine_budget, mesh)
    cache = getattr(index, "_sharded_cache", None)
    if cache is None:
        cache = {}
        index._sharded_cache = cache
    ex = cache.get(key)
    if ex is None:
        si = None
        # share the partitioned+placed index only across entries with the
        # SAME (shards, front, mesh) request — a default (mesh=None) call
        # must not silently adopt a custom-mesh placement and vice versa
        for (sh, _f, _b, _m, _rb, _mesh), other in cache.items():
            if sh == shards and _f == front and _mesh is mesh:
                si = other.sharded
                break
        if si is None:
            ex = ShardedExecutor.from_index(index, shards=shards,
                                            front=front, backend=backend,
                                            mesh=mesh,
                                            micro_batch=micro_batch,
                                            refine_budget=refine_budget)
        else:
            ex = ShardedExecutor(sharded=si, backend=backend,
                                 micro_batch=micro_batch,
                                 refine_budget=refine_budget)
        cache[key] = ex
    return ex
