"""The plain reference and the comparison that decides ``correct``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import data, reference

DATA = {"corpus_seed": 5, "clusters": 8, "spread": 0.35, "decay": 0.7,
        "decay_dims": 2, "query_noise": 0.25}
SPEC = data.Spec({"rows": 3000, "dim": 32, "data": DATA})
LIMITS = {"malformed_answers": 0, "dist_gap": 1e-5, "recall_at_10_min": 0.8}


@pytest.fixture(scope="module")
def corpus():
    x = data.corpus(SPEC)
    q = data.queries(SPEC, np.arange(40))
    return x, q


def _exact(x, q, k=10):
    d = np.sum((np.asarray(x)[None] - np.asarray(q)[:, None]) ** 2, -1)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, 1).astype(np.float32)


def test_corpus_and_query_pool_are_fixed(corpus):
    x, q = corpus
    assert x.shape == (3000, 32) and q.shape == (40, 32)
    assert np.allclose(np.linalg.norm(np.asarray(x), axis=1), 1.0, atol=1e-5)
    assert jnp.array_equal(x, data.corpus(SPEC))
    other = data.Spec({"rows": 3000, "dim": 32,
                       "data": {**DATA, "corpus_seed": 6}})
    assert not jnp.array_equal(x, data.corpus(other))
    assert jnp.array_equal(q, data.queries(SPEC, np.arange(40)))
    assert not jnp.allclose(q, data.queries(other, np.arange(40)))
    w = data.queries(SPEC, np.arange(40), warmup=True)
    assert not jnp.allclose(q, w)
    assert jnp.allclose(q[10:20], data.queries(SPEC, np.arange(10, 20)))


def test_rows_are_the_corpus_rows():
    spec = data.Spec({"rows": 3000, "dim": 32, "data": DATA}).static()
    keys = jax.random.wrap_key_data(data._key_data(5, 3))
    rows = data._rows(keys, jnp.asarray([0, 17, 2999]), spec)
    assert jnp.allclose(rows, data.corpus(SPEC)[jnp.asarray([0, 17, 2999])])


def test_queries_lie_near_their_base_rows(corpus):
    x, q = corpus
    ids, d = _exact(x, q, 1)
    assert np.all(d[:, 0] < 0.2)       # noise of norm 0.25 → d ≈ 0.06


def test_exact_topk_matches_numpy(corpus):
    x, q = corpus
    ids, _ = _exact(x, q)
    assert reference.recall(reference.exact_topk(x, q, 10), ids, 10) == 1.0


def test_recall_on_a_fixed_set():
    truth = np.arange(20).reshape(2, 10)
    pred = truth.copy()
    pred[0, :5] = 100 + np.arange(5)
    pred[1, 1] = pred[1, 0]            # a repeated id counts once
    assert reference.recall(pred, truth, 10) == pytest.approx(14 / 20)


def test_malformed_answers_are_counted():
    good = (np.arange(10), np.linspace(0, 1, 10))
    bad = [None,
           (np.arange(9), np.linspace(0, 1, 9)),
           (np.r_[np.arange(9), 0], np.linspace(0, 1, 10)),
           (np.arange(10), np.linspace(1, 0, 10)),
           (np.r_[np.arange(9), 5000], np.linspace(0, 1, 10)),
           (np.arange(10), np.r_[np.linspace(0, 1, 9), np.inf])]
    ids, ds = zip(*[(a[0], a[1]) if a else (None, None)
                    for a in [good] + bad])
    assert reference.malformed(ids, ds, 3000, 10) == len(bad)


def test_exact_answers_are_correct(corpus):
    x, q = corpus
    ids, d = _exact(x, q)
    v = reference.compare(list(zip(ids, d)), x, q, rows=3000, k=10,
                          limits=LIMITS)
    assert v["correct"], v["checks"]
    assert v["checks"]["dist_gap"]["value"] < 1e-6


def test_the_control_is_not_correct(corpus):
    x, q = corpus
    ids, d = reference.control_answers(x, q, 10)
    v = reference.compare(list(zip(ids, d)), x, q, rows=3000, k=10,
                          limits=LIMITS)
    assert not v["correct"]
    assert v["checks"]["dist_gap"]["value"] > 100 * LIMITS["dist_gap"]


def test_a_swapped_answer_is_not_correct(corpus):
    x, q = corpus
    ids, d = _exact(x, q)
    ans = list(zip(ids, d))
    ans[3], ans[4] = ans[4], ans[3]    # two requests' answers exchanged
    v = reference.compare(ans, x, q, rows=3000, k=10, limits=LIMITS)
    assert not v["correct"] and v["checks"]["dist_gap"]["value"] > 0.01
    ans = list(zip(ids, d))
    ans[5] = None                      # an answer that never came
    v = reference.compare(ans, x, q, rows=3000, k=10, limits=LIMITS)
    assert not v["correct"]
    assert v["checks"]["malformed_answers"]["value"] == 1


def test_reference_ignores_the_default_matmul_precision(corpus):
    x, q = corpus
    with jax.default_matmul_precision("bfloat16"):
        got = reference.exact_topk(x, q, 10)
    assert reference.recall(got, _exact(x, q)[0], 10) == 1.0
