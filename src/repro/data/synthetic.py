"""Synthetic embedding datasets standing in for Wiki-88M / LAION-100M.

The container is offline, so we generate clustered embeddings that match the
statistics that matter for ANNS behaviour: a Gaussian-mixture cluster
structure (so IVF lists are meaningful), anisotropic within-cluster spread
(heavy leading directions, like SBERT/CLIP embeddings after whitening-free
use), and near-unit norms.  Queries are drawn near database points
(in-distribution) plus a fraction of off-distribution noise.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Dataset(NamedTuple):
    x: jax.Array          # (N, D) database vectors
    queries: jax.Array    # (Q, D)
    gt: jax.Array         # (Q, k_gt) exact top-k ids (brute force)


def make_embeddings(key: jax.Array, n: int, d: int, *, clusters: int = 64,
                    spread: float = 0.35, decay: float = 0.7) -> jax.Array:
    """Clustered, anisotropic, ~unit-norm embeddings."""
    k_cent, k_assign, k_noise = jax.random.split(key, 3)
    centers = jax.random.normal(k_cent, (clusters, d))
    centers = centers / jnp.linalg.norm(centers, axis=-1, keepdims=True)
    ids = jax.random.randint(k_assign, (n,), 0, clusters)
    # anisotropic spread: per-dim scale decays (heavy leading dims)
    scales = decay ** (jnp.arange(d) / jnp.maximum(d / 16.0, 1.0))
    noise = jax.random.normal(k_noise, (n, d)) * scales[None, :] * spread
    x = centers[ids] + noise
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def brute_force_topk(x: jax.Array, queries: jax.Array, k: int,
                     *, block: int = 256) -> jax.Array:
    """Exact top-k under L2 (blocked over queries to bound memory).  The
    matmul runs at HIGHEST precision: a TPU's default f32 matmul rounds
    through bf16, which would make the reference approximate."""
    x_sq = jnp.sum(x * x, axis=-1)

    def one_block(qb):
        d = x_sq[None, :] - 2.0 * jnp.matmul(   # + ||q||² (rank-invariant)
            qb, x.T, precision=jax.lax.Precision.HIGHEST)
        _, idx = jax.lax.top_k(-d, k)
        return idx

    blocks = [one_block(queries[i:i + block])
              for i in range(0, queries.shape[0], block)]
    return jnp.concatenate(blocks, axis=0)


def make_dataset(key: jax.Array, *, n: int = 20_000, d: int = 128,
                 n_queries: int = 128, k_gt: int = 100,
                 clusters: int = 64, query_noise: float = 0.25) -> Dataset:
    """Full dataset with exact ground truth for recall evaluation."""
    k_x, k_pick, k_qn = jax.random.split(key, 3)
    x = make_embeddings(k_x, n, d, clusters=clusters)
    pick = jax.random.randint(k_pick, (n_queries,), 0, n)
    q = x[pick] + query_noise * jax.random.normal(k_qn, (n_queries, d)) \
        / jnp.sqrt(d)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    gt = brute_force_topk(x, q, k_gt)
    return Dataset(x=x, queries=q, gt=gt)


def make_token_batch(key: jax.Array, batch: int, seq_len: int,
                     vocab: int) -> dict[str, jax.Array]:
    """Synthetic LM training batch (tokens + next-token labels)."""
    toks = jax.random.randint(key, (batch, seq_len + 1), 0, vocab,
                              dtype=jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
