"""What every generator and reference shares, whatever the metric: keys
from a seed, row blocks, and the tally that turns an answer's checks into
``correct``.

A generator (``generators/<name>.py``) and a reference
(``references/<name>.py``) keep to the protocol in ``run.py``'s docstring;
this module holds none of their semantics.
"""

from __future__ import annotations

import jax
import numpy as np

BLOCK_ROWS = 32_768     # rows a generator makes per step of its corpus map
QUERY_BLOCK = 64        # queries a reference scores per call


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A typed key from a seed of any size (the seed's two 32-bit halves)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def key_data(seed: int, n: int) -> jax.Array:
    """``n`` raw keys split from ``seed_key(seed)``."""
    return jax.random.key_data(jax.random.split(seed_key(seed), n))


def block_rows(rows: int) -> int:
    """Largest divisor of ``rows`` that is at most BLOCK_ROWS."""
    for b in range(min(rows, BLOCK_ROWS), 0, -1):
        if rows % b == 0:
            return b
    return 1


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
    range or repeated, a distance not finite or out of order (a served
    distance is smaller for a nearer row, under every metric)."""
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


def judge(answers: list, x, q, *, rows: int, k: int, limits: dict,
          exact_topk, exact_dist) -> dict:
    """Judge served answers [(ids, distances) or None] for queries q against
    a reference's ``exact_topk(x, q, k)`` (ids) and ``exact_dist(x, q,
    ids)`` (float32 distances of given ids).  Returns the numbers compared
    beside their limits (``checks``), ``correct``, and each answer's count
    of true top-k ids found (``hits``; 0 for a missing or misshapen
    answer)."""
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
