"""Product quantization (Jégou et al.) — FaTRQ's coarse quantizer.

A D-dim vector is split into M subspaces of D/M dims, each quantized with
its own K-entry codebook (K=256 → 1 byte/subspace).  Asymmetric distance
computation (ADC) builds a per-query (M, K) lookup table of partial squared
distances; scoring a code is M table lookups + adds.

These are the "fast memory" structures of Fig. 3: codes (N, M) uint8 and
codebooks (M, K, D/M) stay hot; FaTRQ streams only residual codes from far
memory.

Training and encoding walk the subspaces one at a time (``lax.map``) and
decoding walks row chunks, so no (M, N, ·) temporary is ever built.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.rows import map_rows
from repro.quant.kmeans import assign_rows, kmeans


@functools.partial(jax.tree_util.register_dataclass, data_fields=("codebooks",),
                   meta_fields=())
@dataclass(frozen=True)
class PQCodebook:
    codebooks: jax.Array   # (M, K, Ds)

    @property
    def m(self) -> int:
        return self.codebooks.shape[0]

    @property
    def k(self) -> int:
        return self.codebooks.shape[1]

    @property
    def ds(self) -> int:
        return self.codebooks.shape[2]

    @property
    def dim(self) -> int:
        return self.m * self.ds


def _subspace(x: jax.Array, j, ds: int) -> jax.Array:
    """Columns [j·ds, (j+1)·ds) of x (N, D) — subspace j."""
    return jax.lax.dynamic_slice_in_dim(x, j * ds, ds, axis=1)


@functools.partial(jax.jit, static_argnames=("m", "k", "iters"))
def train(key: jax.Array, x: jax.Array, m: int, k: int = 256,
          iters: int = 20) -> PQCodebook:
    """Train M independent sub-codebooks on x (N, D)."""
    n, d = x.shape
    assert d % m == 0, f"D={d} not divisible by M={m}"
    keys = jax.random.split(key, m)
    books = jax.lax.map(
        lambda a: kmeans(a[1], _subspace(x, a[0], d // m), k, iters),
        (jnp.arange(m), keys))                                # (M, K, Ds)
    return PQCodebook(codebooks=books)


@jax.jit
def encode(cb: PQCodebook, x: jax.Array) -> jax.Array:
    """x (N, D) → codes (N, M) uint8 (K ≤ 256)."""
    ids = jax.lax.map(
        lambda j: assign_rows(_subspace(x, j, cb.ds), cb.codebooks[j]),
        jnp.arange(cb.m))                                     # (M, N)
    return ids.T.astype(jnp.uint8)


@jax.jit
def decode(cb: PQCodebook, codes: jax.Array) -> jax.Array:
    """codes (N, M) → reconstruction x_c (N, D)."""
    def rows(c):
        gathered = jax.vmap(lambda book, ids: book[ids], in_axes=(0, 1))(
            cb.codebooks, c.astype(jnp.int32))               # (M, B, Ds)
        return gathered.transpose(1, 0, 2).reshape(c.shape[0], cb.dim)

    return map_rows(rows, codes)


def adc_table(cb: PQCodebook, q: jax.Array) -> jax.Array:
    """Per-query LUT (M, K): partial ||q_m − c_mk||²."""
    qs = q.reshape(cb.m, 1, cb.ds)
    diff = qs - cb.codebooks                                  # (M, K, Ds)
    return jnp.sum(diff * diff, axis=-1)


def adc_distances(table: jax.Array, codes: jax.Array) -> jax.Array:
    """Score codes (N, M) against a query LUT (M, K) → d̂₀ (N,)."""
    idx = codes.astype(jnp.int32)                             # (N, M)
    part = jax.vmap(lambda t, i: t[i], in_axes=(0, 1), out_axes=1)(table, idx)
    return jnp.sum(part, axis=-1)


def reconstruction_error(cb: PQCodebook, x: jax.Array) -> jax.Array:
    return jnp.mean(jnp.sum((x - decode(cb, encode(cb, x))) ** 2, axis=-1))
