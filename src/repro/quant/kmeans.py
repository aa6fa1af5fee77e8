"""jit-compiled Lloyd's k-means — the shared trainer for IVF coarse
centroids and PQ sub-codebooks.

Distance trick: argmin_c ||x−c||² = argmin_c (||c||² − 2x·c), so assignment
is one matmul (MXU-friendly) — no (N, K, D) intermediate.  Assignment and
the one-hot centroid sums run in row chunks (``core.rows``), so neither the
(N, K) score matrix nor the (N, K) one-hot ever exists whole.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.rows import map_rows, sum_rows


def assign(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """Nearest-centroid ids for x (N, D) against centroids (K, D)."""
    c_sq = jnp.sum(centroids * centroids, axis=-1)           # (K,)
    scores = x @ centroids.T                                  # (N, K) — MXU
    return jnp.argmin(c_sq[None, :] - 2.0 * scores, axis=-1)


@jax.jit
def assign_rows(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """``assign`` over row chunks of x (same ids, bounded scores)."""
    return map_rows(lambda xb: assign(xb, centroids), x)


def _fit(x: jax.Array, centroids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Nearest-centroid ids + squared distance to that centroid."""
    ids = assign(x, centroids)
    return ids, jnp.sum((x - centroids[ids]) ** 2, axis=-1)


def _member_sums(x: jax.Array, ids: jax.Array, k: int
                 ) -> tuple[jax.Array, jax.Array]:
    """Per-centroid sum of members (segment-sum) + member counts."""
    one_hot = jax.nn.one_hot(ids, k, dtype=x.dtype)           # (N, K)
    return one_hot.T @ x, jnp.sum(one_hot, axis=0)            # (K, D), (K,)


def _update(x: jax.Array, ids: jax.Array, k: int
            ) -> tuple[jax.Array, jax.Array]:
    """Mean of members per centroid + member counts."""
    sums, counts = sum_rows(lambda xb, ib: _member_sums(xb, ib, k), x, ids)
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    return means, counts


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans(key: jax.Array, x: jax.Array, k: int, iters: int = 25) -> jax.Array:
    """Train k centroids on x (N, D); k-means++-lite init (random distinct
    samples) then `iters` Lloyd steps.  Empty clusters are re-seeded from the
    point currently farthest from its centroid."""
    n = x.shape[0]
    init_idx = jax.random.choice(key, n, (k,), replace=False)
    centroids = x[init_idx]

    def step(carry, _):
        cents = carry
        ids, d = map_rows(lambda xb: _fit(xb, cents), x)
        means, counts = _update(x, ids, k)
        # re-seed empties at the worst-fit point
        worst = x[jnp.argmax(d)]
        cents = jnp.where((counts > 0)[:, None], means, worst[None, :])
        return cents, None

    centroids, _ = jax.lax.scan(step, centroids, None, length=iters)
    return centroids


def quantization_error(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """Mean squared L2 distortion of the codebook on x."""
    ids = assign(x, centroids)
    return jnp.mean(jnp.sum((x - centroids[ids]) ** 2, axis=-1))
