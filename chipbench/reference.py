"""Plain reference of what the served path must return, and the comparison
that decides ``correct``.

A served answer is the ids of the k corpus rows nearest the query under
squared L2, each with its squared L2 distance, nearest first.  The
reference is exact brute force over the corpus the benchmark generated: a
HIGHEST-precision matmul ranks every row (the TPU's default float32 matmul
rounds through bfloat16), and each served id's distance is recomputed
elementwise in float32.  Nothing of the system under test is imported.

The control is the same reference computed one precision lower (bfloat16),
put in the system's place; ``compare`` must call it not correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 64


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _topk_block(x, x_sq, q, *, k: int, precision):
    s = jnp.matmul(q, x.T, precision=precision)
    _, idx = jax.lax.top_k(2.0 * s - x_sq[None, :], k)   # −||x−q||² + ||q||²
    return idx


def exact_topk(x: jax.Array, q: jax.Array, k: int) -> np.ndarray:
    """(Q, k) ids of the exact nearest rows, blocked over queries."""
    x_sq = jnp.sum(x * x, axis=-1)
    out = [np.asarray(_topk_block(x, x_sq, q[i:i + QUERY_BLOCK], k=k,
                                  precision=jax.lax.Precision.HIGHEST))
           for i in range(0, q.shape[0], QUERY_BLOCK)]
    return np.concatenate(out, axis=0)


@jax.jit
def _dist(x, q, ids):
    safe = jnp.clip(ids, 0, x.shape[0] - 1)
    return jnp.sum((x[safe] - q[:, None, :]) ** 2, axis=-1)


def exact_dist(x: jax.Array, q: jax.Array, ids: np.ndarray) -> np.ndarray:
    """(Q, k) float32 squared L2 of each given id, elementwise."""
    return np.asarray(_dist(x, q, jnp.asarray(ids, jnp.int32)))


@functools.partial(jax.jit, static_argnames=("k",))
def _control_block(x, q, *, k: int):
    xb, qb = x.astype(jnp.bfloat16), q.astype(jnp.bfloat16)
    x_sq = jnp.sum(xb * xb, axis=-1)
    s = jnp.matmul(qb, xb.T, preferred_element_type=jnp.bfloat16)
    _, idx = jax.lax.top_k(2.0 * s - x_sq[None, :], k)
    d = jnp.sum((xb[idx] - qb[:, None, :]) ** 2, axis=-1)
    order = jnp.argsort(d, axis=-1)
    return (jnp.take_along_axis(idx, order, axis=-1),
            jnp.take_along_axis(d, order, axis=-1).astype(jnp.float32))


def control_answers(x: jax.Array, q: jax.Array, k: int
                    ) -> tuple[np.ndarray, np.ndarray]:
    """The reference in bfloat16: (ids, distances) in the system's place."""
    ids, ds = [], []
    for i in range(0, q.shape[0], QUERY_BLOCK):
        a, b = _control_block(x, q[i:i + QUERY_BLOCK], k=k)
        ids.append(np.asarray(a))
        ds.append(np.asarray(b))
    return np.concatenate(ids), np.concatenate(ds)


def recall(pred: np.ndarray, truth: np.ndarray, k: int) -> float:
    """Mean share of each row's true top-k found in its predicted top-k;
    a repeated prediction counts once."""
    if len(truth) == 0:
        return 0.0
    hits = 0
    for p, t in zip(np.asarray(pred)[:, :k], np.asarray(truth)[:, :k]):
        hits += len(set(p.tolist()) & set(t.tolist()))
    return hits / (len(truth) * k)


def malformed(ids, dists, rows: int, k: int) -> int:
    """Answers that break the answer's form: missing, not k ids, an id out of
    range or repeated, a distance not finite or out of order."""
    bad = 0
    for i, d in zip(ids, dists):
        if i is None or d is None:
            bad += 1
            continue
        i, d = np.asarray(i), np.asarray(d)
        if (i.shape != (k,) or d.shape != (k,) or np.any(i < 0)
                or np.any(i >= rows) or len(set(i.tolist())) != k
                or not np.all(np.isfinite(d)) or np.any(np.diff(d) < 0)):
            bad += 1
    return bad


def compare(answers: list, x: jax.Array, q: jax.Array, *, rows: int, k: int,
            limits: dict) -> dict:
    """Judge served answers [(ids, distances) or None] for queries q against
    the exact reference.  Returns the numbers compared beside their limits
    (``checks``), ``correct``, and each answer's count of true top-k ids
    found (``hits``; 0 for a missing or misshapen answer)."""
    bad = malformed([a[0] if a else None for a in answers],
                    [a[1] if a else None for a in answers], rows, k)
    ok = [j for j, a in enumerate(answers)
          if a is not None and np.asarray(a[0]).shape == (k,)]
    hits = [0] * len(answers)
    gap = float("inf")
    if ok:
        ids = np.stack([np.asarray(answers[j][0]) for j in ok])
        served = np.stack([np.asarray(answers[j][1], np.float64)
                           for j in ok])
        qo = q[np.asarray(ok, np.int32)]
        truth = exact_topk(x, qo, k)
        for j, p, t in zip(ok, ids, truth):
            hits[j] = len(set(p.tolist()) & set(t.tolist()))
        d = np.abs(served - exact_dist(x, qo, ids).astype(np.float64))
        gap = float(np.max(np.where(np.isfinite(d), d, np.inf)))
    rec = sum(hits) / (len(answers) * k) if answers else 0.0
    checks = {
        "malformed_answers": {"value": bad, "op": "<=",
                              "limit": limits["malformed_answers"]},
        "dist_gap": {"value": gap, "op": "<=", "limit": limits["dist_gap"]},
        "sample_recall": {"value": rec, "op": ">=",
                          "limit": limits["recall_at_10_min"]},
    }
    correct = (bad <= limits["malformed_answers"]
               and gap <= limits["dist_gap"]
               and rec >= limits["recall_at_10_min"])
    return {"checks": checks, "correct": bool(correct), "hits": hits}
