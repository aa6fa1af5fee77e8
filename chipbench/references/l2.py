"""The squared-L2 reference: the plain reference of what the served path
must return, and the comparison that decides ``correct``.

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

from chipbench.common import QUERY_BLOCK, judge, malformed, recall  # noqa: F401


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


def compare(answers: list, x, q, *, rows: int, k: int, limits: dict) -> dict:
    """Judge served answers [(ids, distances) or None] for queries q against
    the exact reference (``common.judge``)."""
    return judge(answers, x, q, rows=rows, k=k, limits=limits,
                 exact_topk=exact_topk, exact_dist=exact_dist)
