"""Staged search executor tests: backend parity, pluggable front stages,
micro-batching, and the device-counter → QueryCost flow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.anns import (PipelineConfig, build, make_executor, recall_at_k,
                        search)
from repro.anns.executor import SearchExecutor
from repro.anns.stages import (GraphFrontStage, IVFFrontStage,
                               PallasRefineBackend, ReferenceRefineBackend)
from repro.data import make_dataset
from repro.serving import Retriever


@pytest.fixture(scope="module")
def ds():
    return make_dataset(jax.random.PRNGKey(0), n=8000, d=64, n_queries=48,
                        k_gt=100, clusters=32)


@pytest.fixture(scope="module")
def index(ds):
    cfg = PipelineConfig(dim=64, pq_m=8, pq_k=64, nlist=32, nprobe=8,
                         final_k=10, refine_budget=40)
    return build(jax.random.PRNGKey(1), ds.x, cfg)


def _ledger_dict(cost):
    return {k: (t.accesses, t.bytes) for k, t in cost.ledger.items()}


class TestBackendParity:
    def test_identical_topk_ids(self, ds, index):
        # Acceptance: search() produces identical top-k ids under both
        # refinement backends on a fixed-seed synthetic dataset.
        pred_ref, cost_ref = search(index, ds.queries, k=10,
                                    backend="reference")
        pred_pal, cost_pal = search(index, ds.queries, k=10,
                                    backend="pallas")
        assert jnp.array_equal(pred_ref, pred_pal)
        assert _ledger_dict(cost_ref) == _ledger_dict(cost_pal)

    def test_identical_under_quantile_bound(self, ds):
        cfg = PipelineConfig(dim=64, pq_m=8, pq_k=64, nlist=32, nprobe=8,
                             final_k=10, refine_budget=40, bound="quantile")
        idx = build(jax.random.PRNGKey(3), ds.x, cfg)
        a, _ = search(idx, ds.queries, k=10, backend="reference")
        b, _ = search(idx, ds.queries, k=10, backend="pallas")
        assert jnp.array_equal(a, b)

    def test_identical_with_multilevel_trq(self, ds):
        cfg = PipelineConfig(dim=64, pq_m=8, pq_k=64, nlist=32, nprobe=8,
                             final_k=10, refine_budget=40, trq_levels=2)
        idx = build(jax.random.PRNGKey(4), ds.x, cfg)
        a, cost_a = search(idx, ds.queries, k=10, backend="reference")
        b, cost_b = search(idx, ds.queries, k=10, backend="pallas")
        assert jnp.array_equal(a, b)
        assert _ledger_dict(cost_a) == _ledger_dict(cost_b)


class TestFrontStages:
    def test_graph_front_recall_at_least_ivf(self, ds):
        # At a starved nprobe the IVF front misses boundary neighbors; the
        # graph beam front must make up for it (satellite acceptance:
        # graph recall@10 ≥ IVF recall@10 on the small synthetic dataset).
        cfg = PipelineConfig(dim=64, pq_m=8, pq_k=64, nlist=32, nprobe=1,
                             final_k=10, refine_budget=40)
        idx = build(jax.random.PRNGKey(5), ds.x, cfg)
        pred_ivf, _ = search(idx, ds.queries, k=10, front="ivf")
        rec_ivf = recall_at_k(pred_ivf, ds.gt, 10)
        ex = make_executor(idx, front="graph", beam=192, iters=64, expand=8)
        pred_g, _ = ex.search(ds.queries, k=10)
        rec_g = recall_at_k(pred_g, ds.gt, 10)
        assert rec_g >= rec_ivf

    def test_graph_front_cost_ledger(self, ds, index):
        ex = make_executor(index, front="graph")
        _, cost = ex.search(ds.queries, k=10)
        stages = {k.split(":")[0] for k in cost.ledger}
        assert {"front", "coarse", "handoff", "refine", "rerank"} <= stages

    def test_unknown_front_raises(self, index):
        with pytest.raises(ValueError, match="front"):
            SearchExecutor.from_index(index, front="lsh")

    def test_unknown_backend_raises(self, index):
        with pytest.raises(ValueError, match="backend"):
            SearchExecutor.from_index(index, backend="cuda")


class TestMicroBatching:
    def test_results_and_ledger_invariant(self, ds, index):
        full = make_executor(index)
        micro = make_executor(index, micro_batch=7)   # does not divide 48
        a, cost_a = full.search(ds.queries, k=10)
        b, cost_b = micro.search(ds.queries, k=10)
        assert jnp.array_equal(a, b)
        assert _ledger_dict(cost_a) == _ledger_dict(cost_b)

    def test_serving_retriever(self, ds, index):
        r = Retriever(index=index, micro_batch=8)
        ids, cost = r.retrieve(ds.queries[:16], k=5)
        assert ids.shape == (16, 5)
        assert cost.total_seconds() > 0
        r.retrieve(ds.queries[:16], k=5)
        # running ledger accumulates across calls
        assert r.total_cost.ledger["rerank:ssd"].accesses == \
            2 * cost.ledger["rerank:ssd"].accesses


class TestMultiLevelTraffic:
    def test_deeper_levels_charged_actual_survivors(self, ds):
        # Level ℓ ≥ 1 codes stream only for survivors of level ℓ−1, so the
        # ledger must charge the per-level entering counts emitted by the
        # backends (refine_alive_l{ℓ}) — NOT the final survivor count,
        # which under-charges every intermediate level (the alive chain
        # only shrinks).
        cfg = PipelineConfig(dim=64, pq_m=8, pq_k=64, nlist=32, nprobe=8,
                             final_k=10, refine_budget=40, trq_levels=3)
        idx = build(jax.random.PRNGKey(6), ds.x, cfg)
        ex = make_executor(idx)
        cand = ex.front.candidates(ds.queries)
        refined = ex.backend.refine(ds.queries, cand, idx.trq, k=10,
                                    bound=cfg.bound, z=cfg.z)
        n_l1 = int(refined.counters["refine_alive_l1"])
        n_l2 = int(refined.counters["refine_alive_l2"])
        n_final = int(refined.counters["refine_alive"])
        assert n_l1 >= n_l2 >= n_final          # monotone pruning chain
        _, cost = ex.search(ds.queries, k=10)
        n_cand = cost.ledger["coarse:hbm"].accesses
        assert cost.ledger["refine:cxl"].accesses == n_cand + n_l1 + n_l2
        # bytes bill at the tier's min transfer grain when records are small
        from repro.memory import Tier
        per_access = max(idx.layout.far_bytes,
                         cost.model[Tier.CXL].min_grain_B)
        assert cost.ledger["refine:cxl"].bytes == \
            (n_cand + n_l1 + n_l2) * per_access


class TestCostFlow:
    def test_counters_are_device_side(self, ds, index):
        cand = make_executor(index).front.candidates(ds.queries[:4])
        assert all(isinstance(v, jax.Array) for v in cand.counters.values())

    def test_facade_matches_executor(self, ds, index):
        a, cost_a = search(index, ds.queries, k=10)
        b, cost_b = make_executor(index).search(ds.queries, k=10)
        assert jnp.array_equal(a, b)
        assert _ledger_dict(cost_a) == _ledger_dict(cost_b)

    def test_executor_matches_legacy_ledger_shape(self, ds, index):
        _, cost = search(index, ds.queries, k=10)
        stages = {k.split(":")[0] for k in cost.ledger}
        assert stages == {"coarse", "handoff", "refine", "rerank"}
        # stage ordering of traffic magnitudes: every candidate streams
        # level-0 codes; only ≤ budget·Q survivors hit SSD
        assert cost.ledger["refine:cxl"].accesses == \
            cost.ledger["coarse:hbm"].accesses
        assert cost.ledger["rerank:ssd"].accesses <= 40 * ds.queries.shape[0]


class TestGraphPrimitives:
    """index/graph.py building blocks: the vectorized build against a
    per-edge reference loop, the per-degree graph cache, and the online
    maintenance ops (insert_nodes / compact_graph) the streaming layer
    relies on."""

    @staticmethod
    def _build_reference(x, degree):
        """graph.build's algorithm with per-edge Python loops: same kNN
        pruning, same (source, rank) reverse-edge acceptance order, same
        forward-edge padding and shortcut rng — the spec the vectorized
        scatter must reproduce bit for bit."""
        from repro.data.synthetic import brute_force_topk

        n = x.shape[0]
        fwd = int(degree * 3 / 4)
        knn = np.asarray(brute_force_topk(x, x, degree + 1))
        mask = knn != np.arange(n)[:, None]
        order = np.argsort(~mask, axis=1, kind="stable")
        pruned = np.take_along_axis(knn, order, axis=1)[:, :degree]
        neighbors = np.full((n, degree), -1, np.int32)
        neighbors[:, :fwd] = pruned[:, :fwd]
        fill = np.full(n, fwd)
        for i in range(n):                      # reverse edges, edge order
            for j in pruned[i, :fwd]:
                if fill[j] < degree:
                    neighbors[j, fill[j]] = i
                    fill[j] += 1
        for i in range(n):                      # pad with forward edges
            for c in range(fill[i], degree):
                neighbors[i, c] = pruned[i, min(fwd + c - fill[i],
                                                degree - 1)]
        rng = np.random.default_rng(7)
        neighbors[:, degree - 2:] = rng.integers(0, n, size=(n, 2))
        return neighbors.astype(np.int32)

    def test_vectorized_build_matches_reference_loop(self):
        from repro.index import graph as graph_mod

        x = jax.random.normal(jax.random.PRNGKey(11), (400, 16))
        got = np.asarray(graph_mod.build(x, degree=8).neighbors)
        want = self._build_reference(x, 8)
        np.testing.assert_array_equal(got, want)

    def test_graph_for_caches_per_degree(self, index):
        from repro.anns.stages import graph_for

        g16 = graph_for(index)
        assert graph_for(index) is g16             # cache hit
        g8 = graph_for(index, degree=8)
        assert g8 is not g16                       # degree keys the cache
        assert g8.neighbors.shape == (index.x.shape[0], 8)
        assert graph_for(index, degree=8) is g8
        assert graph_for(index, degree=16) is g16  # earlier entry survives

    def test_insert_nodes_invariants(self, ds):
        from repro.index import graph as graph_mod

        x = np.asarray(ds.x[:500], np.float32)
        n_old, n = 460, 500
        g0 = np.asarray(graph_mod.build(x[:n_old], degree=8).neighbors)
        g1 = graph_mod.insert_nodes(g0, x, n_old)
        assert g1.shape == (n, 8) and g1.dtype == np.int32
        assert (g1 >= 0).all() and (g1 < n).all()
        # new rows were wired against the PRE-batch graph: their forward
        # edges can only point at pre-existing rows
        assert (g1[n_old:] < n_old).all()
        # pre-batch rows change only by reverse-edge replacement, and a
        # replaced slot always points at an inserted row
        changed = g1[:n_old] != g0
        assert (g1[:n_old][changed] >= n_old).all()
        # deterministic: same inputs, same adjacency
        np.testing.assert_array_equal(g1, graph_mod.insert_nodes(g0, x,
                                                                 n_old))

    def test_insert_single_node_gets_reverse_edge(self, ds):
        from repro.index import graph as graph_mod

        x = np.asarray(ds.x[:301], np.float32)
        g0 = np.asarray(graph_mod.build(x[:300], degree=8).neighbors)
        g1 = graph_mod.insert_nodes(g0, x, 300)
        # the j==0 reverse edge is unconditional, so a freshly inserted
        # node is immediately reachable from its nearest beam hit
        assert (g1[:300] == 300).any()

    def test_insert_nodes_rejects_wrong_n_old(self, ds):
        from repro.index import graph as graph_mod

        x = np.asarray(ds.x[:300], np.float32)
        g0 = np.asarray(graph_mod.build(x[:290], degree=8).neighbors)
        with pytest.raises(ValueError, match="n_old"):
            graph_mod.insert_nodes(g0, x, 280)

    def test_compact_graph_invariants(self, ds):
        from repro.index import graph as graph_mod

        x = np.asarray(ds.x[:400], np.float32)
        g = np.asarray(graph_mod.build(x, degree=8).neighbors)
        dead = np.arange(50, 130)
        live = np.setdiff1d(np.arange(400), dead)
        out = graph_mod.compact_graph(g, x, live)
        assert out.shape == (live.size, 8) and out.dtype == np.int32
        # no dangling edges: everything points at a live, renumbered row
        assert (out >= 0).all() and (out < live.size).all()
        # rows whose edges were all live are a pure renumbering
        new_of = np.full(400, -1, np.int32)
        new_of[live] = np.arange(live.size, dtype=np.int32)
        direct = new_of[g[live]]
        untouched = (direct >= 0).all(axis=1)
        assert untouched.any()
        np.testing.assert_array_equal(out[untouched], direct[untouched])

    def test_compact_graph_rejects_empty(self, ds):
        from repro.index import graph as graph_mod

        x = np.asarray(ds.x[:50], np.float32)
        g = np.asarray(graph_mod.build(x, degree=8).neighbors)
        with pytest.raises(ValueError, match="zero live rows"):
            graph_mod.compact_graph(g, x, np.array([], np.int64))


class TestADCKernelPath:
    """Every front scores its candidates through ``stages.adc_score``; on a
    TPU that is the MXU kernel of ``kernels/pq_adc.py``.  Here the platform
    choice is steered onto the kernel (run in interpret mode) and each
    call's d̂₀ is checked against the table gather on the same codes."""

    @pytest.fixture(scope="class")
    def small(self):
        from repro.anns import StreamingConfig, StreamingIndex
        d = make_dataset(jax.random.PRNGKey(0), n=2500, d=32, n_queries=8,
                         k_gt=20, clusters=8)
        cfg = PipelineConfig(dim=32, pq_m=4, pq_k=32, nlist=16, nprobe=4,
                             final_k=5, refine_budget=20)
        st = StreamingIndex(build(jax.random.PRNGKey(2), d.x[:2000], cfg),
                            StreamingConfig(auto_compact=False))
        st.insert(d.x[2000:])                 # delta lists
        st.delete(np.arange(100, 200))        # tombstones
        return d, build(jax.random.PRNGKey(1), d.x, cfg), st

    @staticmethod
    def _on_kernel(mp):
        """Route ``adc_score`` onto the kernel; return the list each
        kernel call appends (kernel d̂₀, gather d̂₀) to."""
        from repro.kernels import pq_adc
        from repro.quant import pq
        seen = []
        kernel = pq_adc.pq_adc_batch

        def spy(codes, tables):
            d0 = kernel(codes, tables)
            ref = jax.vmap(pq.adc_distances)(tables, codes)
            jax.debug.callback(
                lambda a, b: seen.append((np.asarray(a), np.asarray(b))),
                d0, ref)
            return d0

        mp.setattr(pq_adc, "use_kernel", lambda: True)
        mp.setattr(pq_adc, "pq_adc_batch", spy)
        jax.clear_caches()
        return seen

    @pytest.mark.parametrize("front", ["ivf", "graph"])
    def test_candidates_match_gather(self, small, front, monkeypatch):
        ds_, idx, _ = small
        stage = make_executor(idx, front=front).front
        qvalid = jnp.arange(ds_.queries.shape[0]) < 6     # two padded rows
        want = stage.candidates(ds_.queries, qvalid)
        with monkeypatch.context() as mp:
            seen = self._on_kernel(mp)
            got = stage.candidates(ds_.queries, qvalid)
            jax.effects_barrier()
        jax.clear_caches()
        assert len(seen) == 1
        np.testing.assert_allclose(seen[0][0], seen[0][1], rtol=1e-5,
                                   atol=1e-6)
        assert jnp.array_equal(got.ids, want.ids)
        assert jnp.array_equal(got.valid, want.valid)
        assert bool(jnp.all(jnp.where(got.valid, True,
                                      got.d0 == jnp.inf)))
        assert not bool(jnp.all(got.valid))      # some slots are invalid
        np.testing.assert_allclose(np.asarray(got.d0), np.asarray(want.d0),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("layout", ["static", "sharded", "streaming"])
    @pytest.mark.parametrize("front", ["ivf", "graph"])
    def test_search_ids_unchanged(self, small, layout, front, monkeypatch):
        from repro.anns import Database, QueryPlan
        ds_, idx, st = small
        db = Database.wrap(st if layout == "streaming" else idx)
        plan = QueryPlan(front=front, backend="pallas",
                         shards=1 if layout == "sharded" else None)
        want = db.query(ds_.queries, plan=plan)
        with monkeypatch.context() as mp:
            seen = self._on_kernel(mp)
            got = db.query(ds_.queries, plan=plan)
            jax.effects_barrier()
        jax.clear_caches()
        assert seen, "the front did not score through the kernel"
        for d0, ref in seen:
            np.testing.assert_allclose(d0, ref, rtol=1e-5, atol=1e-6)
        assert jnp.array_equal(got.ids, want.ids)
        np.testing.assert_array_equal(np.asarray(got.distances),
                                      np.asarray(want.distances))
