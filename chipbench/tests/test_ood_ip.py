"""The ``ood_ip`` generator and the inner-product reference at a CPU test's
size: seed-pure rows and queries, exact top-k, the two properties the data
is built to have, and the verdicts on sound answers, on the control and on
a system that ranks by squared L2."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common, control, pair, run
from chipbench.tests import tiny

gen = run.load("generators", "ood_ip")
ip = run.load("references", "ip")
l2 = run.load("references", "l2")

ROWS, DIM, HELD = 20_000, 200, 2_000
DATA = dict(gen.DEFAULTS, corpus_seed=5)
SPEC = gen.Spec({"rows": ROWS, "dim": DIM, "data": DATA})
LIMITS = {"malformed_answers": 0, "dist_gap": 1e-4, "recall_at_10_min": 0.8}


@pytest.fixture(scope="module")
def corpus():
    return gen.corpus(SPEC), np.asarray(gen.queries(SPEC, np.arange(256)))


def _exact(x, q, k=10):
    """Top-k by inner product in float64 NumPy: ids, and −⟨q, x⟩ as
    float32."""
    s = np.asarray(q, np.float64) @ np.asarray(x, np.float64).T
    ids = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return ids, (-np.take_along_axis(s, ids, 1)).astype(np.float32)


def test_rows_and_queries_are_pure_functions_of_seed_and_index(corpus):
    x, q = corpus
    assert x.shape == (ROWS, DIM) and q.shape == (256, DIM)
    assert jnp.array_equal(x, gen.corpus(SPEC))
    keys = jax.random.wrap_key_data(gen.key_data(5, gen.N_KEYS)[gen.ROW_KEYS])
    idx = jnp.asarray([0, 17, ROWS - 1])
    assert jnp.allclose(gen._rows(keys, idx, SPEC.static()), x[idx])
    # the harness makes queries in aligned blocks; another batch may round
    # the map's matmul otherwise in the last bit
    assert np.allclose(q[100:140], gen.queries(SPEC, np.arange(100, 140)),
                       rtol=1e-6, atol=1e-6)
    other = gen.Spec({"rows": ROWS, "dim": DIM,
                      "data": dict(DATA, corpus_seed=6)})
    assert not jnp.allclose(x, gen.corpus(other))
    assert not np.allclose(q, gen.queries(other, np.arange(256)))
    # rows are not normalised: their norms spread about a median of 1
    norms = np.linalg.norm(np.asarray(x), axis=1)
    assert 0.9 < np.median(norms) < 1.1 and np.std(np.log(norms)) > 0.2


def test_the_warm_up_and_measured_pools_are_disjoint(corpus):
    _, q = corpus
    w = np.asarray(gen.queries(SPEC, np.arange(256), warmup=True))
    assert np.array_equal(w, gen.queries(SPEC, np.arange(256), warmup=True))
    gap = np.linalg.norm(q[:, None, :] - w[None, :, :], axis=-1)
    assert gap.min() > 1e-3


@pytest.mark.parametrize("row_block", [ip.ROW_BLOCK, 6_000])
def test_exact_topk_matches_numpy_float64(corpus, row_block):
    x, q = corpus
    got = ip.exact_topk(x, q, 10, row_block=row_block)
    assert common.recall(got, _exact(x, q)[0], 10) == 1.0
    # a corpus on the host gives the same answers
    host = ip.exact_topk(np.asarray(x), q[:64], 10, row_block=row_block)
    assert np.array_equal(host, got[:64])


def test_l2_topk_blocked_over_rows_matches_the_l2_reference(corpus):
    x, q = corpus
    assert common.recall(pair.l2_topk(x, q, 10, 6_000),
                         l2.exact_topk(x, q, 10), 10) == 1.0


def test_inner_product_ranks_otherwise_than_l2(corpus):
    """Property (a): the exact L2 top-10 holds at most half of the exact
    inner-product top-10, so an engine that ranks by L2 fails a recall
    floor of 0.80 by a wide margin."""
    x, q = corpus
    overlap = common.recall(pair.l2_topk(x, q, 10, ip.ROW_BLOCK),
                            ip.exact_topk(x, q, 10), 10)
    assert overlap <= 0.5


def test_queries_are_out_of_distribution(corpus):
    """Property (b): under the rows' mean and covariance, the queries' median
    Mahalanobis distance is at least 1.5 times that of held-out rows."""
    x, q = corpus
    x = np.asarray(x)
    m = pair.mahalanobis_ratio(x[:ROWS - HELD], x[ROWS - HELD:], q)
    assert m["ratio"] >= 1.5


def test_exact_answers_are_correct(corpus):
    x, q = corpus
    ids, d = _exact(x, q)
    v = ip.compare(list(zip(ids, d)), x, q, rows=ROWS, k=10, limits=LIMITS)
    assert v["correct"], v["checks"]
    assert v["checks"]["dist_gap"]["value"] < 1e-5
    ids, d = ip.exact_answers(x, q, 10)          # as its matmul scored them
    v = ip.compare(list(zip(ids, d)), x, q, rows=ROWS, k=10, limits=LIMITS)
    assert v["correct"], v["checks"]


def test_the_control_is_not_correct(corpus):
    x, q = corpus
    ids, d = ip.control_answers(x, q, 10)
    v = ip.compare(list(zip(ids, d)), x, q, rows=ROWS, k=10, limits=LIMITS)
    assert not v["correct"]
    assert v["checks"]["dist_gap"]["value"] > 10 * LIMITS["dist_gap"]


def test_a_served_inner_product_is_not_a_distance(corpus):
    """+⟨q, x⟩ best first is out of order, and every gap is twice it."""
    x, q = corpus
    ids, d = _exact(x, q)
    v = ip.compare(list(zip(ids, -d)), x, q, rows=ROWS, k=10, limits=LIMITS)
    assert not v["correct"]
    assert v["checks"]["malformed_answers"]["value"] == len(q)
    assert v["checks"]["dist_gap"]["value"] > 0.5


def _run(**kw):
    return run.run_cell(tiny.ood_parts(), tiny.SEED, 0.5, False,
                        require_tpu=False, t_start=time.perf_counter(), **kw)


def test_an_l2_system_is_not_correct_on_inner_product_data():
    """Today's system ranks by squared L2: served on ``ood_ip`` data and
    judged by the inner-product reference, it fails."""
    res = _run()
    assert not res["correct"]
    checks = res["checks"]
    assert checks["dist_gap"]["value"] > checks["dist_gap"]["limit"]
    assert checks["sample_recall"]["value"] < 0.5


def test_the_control_in_the_window_is_not_correct():
    res = _run(system=control.system)
    assert not res["correct"]
    assert res["checks"]["dist_gap"]["value"] > \
        res["checks"]["dist_gap"]["limit"]
    assert res["checks"]["malformed_answers"]["value"] == 0
