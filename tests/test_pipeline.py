"""End-to-end behaviour tests for the FaTRQ-augmented ANNS system."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.anns import (PipelineConfig, baseline_search, build, recall_at_k,
                        search)
from repro.data import make_dataset
from repro.index import graph, ivf
from repro.memory import QueryCost, Tier


@pytest.fixture(scope="module")
def ds():
    return make_dataset(jax.random.PRNGKey(0), n=8000, d=64, n_queries=48,
                        k_gt=100, clusters=32)


@pytest.fixture(scope="module")
def index(ds):
    cfg = PipelineConfig(dim=64, pq_m=8, pq_k=64, nlist=32, nprobe=8,
                         final_k=10, refine_budget=40)
    return build(jax.random.PRNGKey(1), ds.x, cfg)


class TestIVF:
    def test_probe_contains_true_neighbors(self, ds):
        idx = ivf.build(jax.random.PRNGKey(2), ds.x, nlist=32)
        cand = ivf.probe_batch(idx, ds.queries, nprobe=8)
        hit = 0
        for i in range(ds.queries.shape[0]):
            c = set(np.asarray(cand[i]).tolist())
            g = set(np.asarray(ds.gt[i, :10]).tolist())
            hit += len(g & c) / 10
        assert hit / ds.queries.shape[0] > 0.8

    def test_lists_partition_database(self, ds):
        idx = ivf.build(jax.random.PRNGKey(2), ds.x, nlist=32)
        members = np.asarray(idx.lists)
        members = members[members >= 0]
        assert len(np.unique(members)) >= 0.99 * ds.x.shape[0]  # cap loss <1%


class TestGraph:
    def test_beam_search_recall(self, ds):
        g = graph.build(ds.x, degree=16)
        res = graph.search_batch(g, ds.x, ds.queries, iters=48, beam=64)
        rec = recall_at_k(res[:, :10], ds.gt, 10)
        assert rec > 0.8


class TestPipeline:
    def test_recall_vs_ground_truth(self, ds, index):
        # Budget-capped mode (the paper's operating point, Fig. 8): small
        # recall loss allowed in exchange for few SSD fetches.
        pred, _ = search(index, ds.queries, k=10)
        rec = recall_at_k(pred, ds.gt, 10)
        base, _ = baseline_search(index, ds.queries, k=10)
        rec_base = recall_at_k(base, ds.gt, 10)
        assert rec >= rec_base - 0.03

    def test_cauchy_pruning_is_lossless_without_budget_cap(self, ds):
        # With an open budget, provable pruning must match the baseline
        # exactly: only candidates certified outside top-k are dropped.
        cfg = PipelineConfig(dim=64, pq_m=8, pq_k=64, nlist=32, nprobe=8,
                             final_k=10, refine_budget=750)
        idx = build(jax.random.PRNGKey(9), ds.x, cfg)
        pred, cost = search(idx, ds.queries, k=10)
        base, _ = baseline_search(idx, ds.queries, k=10)
        assert recall_at_k(pred, ds.gt, 10) == recall_at_k(base, ds.gt, 10)
        # and pruning still removed a sizable share of SSD fetches
        ssd = sum(t.accesses for k_, t in cost.ledger.items()
                  if k_.endswith("ssd"))
        assert ssd < 0.6 * 750 * ds.queries.shape[0]

    def test_ssd_traffic_reduced(self, ds, index):
        _, cost = search(index, ds.queries, k=10)
        _, cost_base = baseline_search(index, ds.queries, k=10)
        ssd = sum(t.accesses for k_, t in cost.ledger.items()
                  if k_.endswith("ssd"))
        ssd_base = sum(t.accesses for k_, t in cost_base.ledger.items()
                       if k_.endswith("ssd"))
        assert ssd < 0.5 * ssd_base   # paper: ~2.8× fewer refinement fetches

    def test_throughput_improves(self, ds, index):
        _, cost = search(index, ds.queries, k=10)
        _, cost_base = baseline_search(index, ds.queries, k=10)
        assert cost.total_seconds() < cost_base.total_seconds()

    def test_quantile_bound_mode(self, ds):
        cfg = PipelineConfig(dim=64, pq_m=8, pq_k=64, nlist=32, nprobe=8,
                             final_k=10, refine_budget=40, bound="quantile")
        idx = build(jax.random.PRNGKey(3), ds.x, cfg)
        pred, _ = search(idx, ds.queries, k=10)
        assert recall_at_k(pred, ds.gt, 10) > 0.6

    def test_multilevel_trq(self, ds):
        cfg = PipelineConfig(dim=64, pq_m=8, pq_k=64, nlist=32, nprobe=8,
                             final_k=10, refine_budget=40, trq_levels=2)
        idx = build(jax.random.PRNGKey(4), ds.x, cfg)
        pred, cost = search(idx, ds.queries, k=10)
        assert recall_at_k(pred, ds.gt, 10) > 0.6


class TestCostModel:
    def test_overlap_model_is_max_of_latency_and_bandwidth(self):
        # tier_seconds uses max(lat, bw): queue-amortized access latency and
        # streaming transfer fully overlap — the stage is bound by whichever
        # is larger, never their sum.
        spec = QueryCost().model[Tier.CXL]
        # latency-bound: many minimum-grain accesses
        c = QueryCost()
        c.record("s", Tier.CXL, 100_000, 1)
        lat = 100_000 * spec.latency_s / spec.parallelism
        bw = 100_000 * spec.min_grain_B / spec.bandwidth_Bps
        assert lat > bw
        assert c.tier_seconds(Tier.CXL) == pytest.approx(max(lat, bw))
        # bandwidth-bound: few huge transfers
        c2 = QueryCost()
        c2.record("s", Tier.CXL, 10, 10_000_000)
        lat2 = 10 * spec.latency_s / spec.parallelism
        bw2 = 10 * 10_000_000 / spec.bandwidth_Bps
        assert bw2 > lat2
        assert c2.tier_seconds(Tier.CXL) == pytest.approx(max(lat2, bw2))

    def test_tier_ordering(self):
        c = QueryCost()
        c.record("s", Tier.SSD, 100, 4096)
        ssd_t = c.tier_seconds(Tier.SSD)
        c2 = QueryCost()
        c2.record("s", Tier.CXL, 100, 4096)
        assert c2.tier_seconds(Tier.CXL) < ssd_t

    def test_grain_rounding(self):
        c = QueryCost()
        c.record("s", Tier.SSD, 10, 100)   # 100 B reads cost 4 KiB each
        t = [v for k, v in c.ledger.items() if k.endswith("ssd")][0]
        assert t.bytes == 10 * 4096


class TestChunkedBuild:
    """The build's row chunking bounds device temporaries at deployment
    scale; it must not change the index.  One chunk covering N against
    several chunks with a short tail (2000 = 3·600 + 200)."""

    @pytest.fixture(scope="class")
    def rows(self):
        return make_dataset(jax.random.PRNGKey(3), n=2000, d=40,
                            n_queries=4, k_gt=10, clusters=8).x

    @pytest.mark.parametrize("part", ["pq_codes", "trq_packed",
                                      "ivf_lists"])
    def test_one_chunk_equals_several(self, rows, part, monkeypatch):
        from repro.core import rows as rows_mod
        from repro.core import trq as trq_mod
        from repro.quant import pq

        def built(chunk):
            # ROW_CHUNK is read when the build programs are traced
            monkeypatch.setattr(rows_mod, "ROW_CHUNK", chunk)
            jax.clear_caches()
            if part == "ivf_lists":
                idx = ivf.build(jax.random.PRNGKey(2), rows, nlist=16)
                return [idx.lists, idx.list_len, idx.centroids]
            cb = pq.train(jax.random.PRNGKey(4), rows, m=4, k=32, iters=6)
            codes = pq.encode(cb, rows)
            if part == "pq_codes":
                return [codes, cb.codebooks]
            x_c = pq.decode(cb, codes)
            trq, _ = trq_mod.encode_database(rows, x_c, num_levels=2)
            return [lv.packed for lv in trq.levels] + [trq.scalars.norm]

        one, several = built(1 << 20), built(600)
        jax.clear_caches()
        for a, b in zip(one[:-1], several[:-1]):
            assert jnp.array_equal(a, b)
        # float sums over rows may differ in their last bits only
        np.testing.assert_allclose(np.asarray(one[-1]),
                                   np.asarray(several[-1]),
                                   rtol=1e-6, atol=1e-6)
