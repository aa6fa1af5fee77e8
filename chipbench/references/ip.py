"""The inner-product reference: the plain reference of what the served path
must return, and the comparison that decides ``correct``.

A served answer is the ids of the k corpus rows with the largest inner
product ⟨q, x⟩, each with its distance −⟨q, x⟩, best first (a served
distance is smaller for a nearer row under every metric).  The reference
is exact brute force over the corpus the benchmark generated: a
HIGHEST-precision matmul scores every row (the TPU's default float32
matmul rounds through bfloat16), blocked over queries and over rows so
that a corpus of several GB and its scores fit one chip beside each other;
each served id's distance is recomputed elementwise in float32.  The
corpus may live on the device or on the host: each block of rows is
sliced from it and read once a call.  Nothing of the system under test is
imported.

The control is the same reference computed one precision lower (bfloat16),
put in the system's place; ``compare`` must call it not correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.common import QUERY_BLOCK, judge, malformed, recall  # noqa: F401

ROW_BLOCK = 1 << 20      # rows scored per call: 0.8 GB of 200-d float32


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _score_block(xb, q, *, k: int, precision):
    """(Q, k) largest scores ⟨q, x⟩ over a block of rows, and their ids in
    the block."""
    return jax.lax.top_k(jnp.matmul(q, xb.T, precision=precision), k)


@functools.partial(jax.jit, static_argnames=("k",))
def _control_score_block(xb, q, *, k: int):
    xb, qb = xb.astype(jnp.bfloat16), q.astype(jnp.bfloat16)
    return jax.lax.top_k(
        jnp.matmul(qb, xb.T, preferred_element_type=jnp.bfloat16), k)


@functools.partial(jax.jit, static_argnames=("k",))
def _merge(s_a, i_a, s_b, i_b, *, k: int):
    s = jnp.concatenate([s_a, s_b], axis=1)
    top, pos = jax.lax.top_k(s, k)
    return top, jnp.take_along_axis(jnp.concatenate([i_a, i_b], axis=1),
                                    pos, axis=1)


def _blocked_topk(x, q, k: int, score, row_block: int):
    """(scores, ids), each (Q, k), of the k rows of ``x`` that ``score``
    ranks highest for each query: rows in blocks of ``row_block``, each
    block read once and scored against every block of QUERY_BLOCK
    queries."""
    q = jnp.asarray(q, jnp.float32)
    starts = range(0, q.shape[0], QUERY_BLOCK)
    best = {i: None for i in starts}
    for r0 in range(0, x.shape[0], row_block):
        xb = jnp.asarray(x[r0:r0 + row_block])
        kb = min(k, xb.shape[0])
        for i in starts:
            s, ids = score(xb, q[i:i + QUERY_BLOCK], k=kb)
            ids = ids + r0
            best[i] = (s, ids) if best[i] is None else \
                _merge(*best[i], s, ids, k=min(k, r0 + xb.shape[0]))
    return (np.concatenate([np.asarray(best[i][0]) for i in starts]),
            np.concatenate([np.asarray(best[i][1]) for i in starts]))


def exact_answers(x, q, k: int, *, row_block: int = ROW_BLOCK
                  ) -> tuple[np.ndarray, np.ndarray]:
    """(Q, k) ids of the exact top-k rows by inner product, and their
    distances −⟨q, x⟩ as the HIGHEST-precision matmul scored them."""
    hi = functools.partial(_score_block, precision=jax.lax.Precision.HIGHEST)
    s, ids = _blocked_topk(x, q, k, hi, row_block)
    return ids, -s


def exact_topk(x, q, k: int, *, row_block: int = ROW_BLOCK) -> np.ndarray:
    """(Q, k) ids of the exact top-k rows by inner product."""
    return exact_answers(x, q, k, row_block=row_block)[0]


def _gather(x, ids: np.ndarray):
    """Rows ``ids`` (Q, k) of ``x`` → (Q, k, D), wherever ``x`` lives."""
    return x[np.clip(np.asarray(ids), 0, x.shape[0] - 1)]


@jax.jit
def _dist(rows, q):
    return -jnp.sum(rows * q[:, None, :], axis=-1)


def exact_dist(x, q, ids: np.ndarray) -> np.ndarray:
    """(Q, k) float32 −⟨q, x⟩ of each given id, elementwise."""
    return np.asarray(_dist(jnp.asarray(_gather(x, ids)),
                            jnp.asarray(q, jnp.float32)))


@jax.jit
def _control_dist(rows, q):
    d = -jnp.sum(rows.astype(jnp.bfloat16)
                 * q.astype(jnp.bfloat16)[:, None, :], axis=-1)
    order = jnp.argsort(d, axis=-1)
    return order, jnp.take_along_axis(d, order, axis=-1).astype(jnp.float32)


def control_answers(x, q, k: int) -> tuple[np.ndarray, np.ndarray]:
    """The reference in bfloat16: (ids, distances) in the system's place."""
    _, ids = _blocked_topk(x, q, k, _control_score_block, ROW_BLOCK)
    order, d = _control_dist(jnp.asarray(_gather(x, ids)),
                             jnp.asarray(q, jnp.float32))
    return np.take_along_axis(ids, np.asarray(order), axis=1), np.asarray(d)


def compare(answers: list, x, q, *, rows: int, k: int, limits: dict) -> dict:
    """Judge served answers [(ids, distances) or None] for queries q against
    the exact reference (``common.judge``)."""
    return judge(answers, x, q, rows=rows, k=k, limits=limits,
                 exact_topk=exact_topk, exact_dist=exact_dist)
