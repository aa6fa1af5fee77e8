"""Row-chunked evaluation for build passes over a whole table.

Per-row work over N rows (nearest-centroid assignment, ternary encoding,
PQ decoding) runs ``ROW_CHUNK`` rows at a time (``map_rows``), so a temporary
that is N rows by something wide — an (N, nlist) score matrix, an (N, D)
sort — exists for one chunk only.  Each such function is row-independent,
so its result does not depend on the chunk size.  Reductions over rows
(``sum_rows``) add per-chunk partial sums; there the chunk size changes
only the order of the floating-point additions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Rows per chunk: an (N, 1024) f32 score matrix is 128 MiB per chunk.
#: Read when a caller is traced.
ROW_CHUNK = 32_768


def map_rows(f, *xs: jax.Array):
    """``f(*xs)`` evaluated ``ROW_CHUNK`` rows at a time (a ``lax.map``
    over chunks plus one short tail); every output leaf of f has a leading
    row axis.  ``ROW_CHUNK ≥ N`` is a single call."""
    n, chunk = xs[0].shape[0], ROW_CHUNK
    if chunk >= n:
        return f(*xs)
    nc = n // chunk

    def body(i):
        return f(*(jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk)
                   for x in xs))

    out = jax.lax.map(body, jnp.arange(nc))
    out = jax.tree.map(lambda a: a.reshape(nc * chunk, *a.shape[2:]), out)
    if n % chunk:
        tail = f(*(x[nc * chunk:] for x in xs))
        out = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), out, tail)
    return out


def sum_rows(f, *xs: jax.Array):
    """Σ over ``ROW_CHUNK``-row chunks of ``f(*chunk_of_xs)`` (a
    ``lax.scan`` over chunks plus one short tail).  ``ROW_CHUNK ≥ N`` is a
    single call."""
    n, chunk = xs[0].shape[0], ROW_CHUNK
    if chunk >= n:
        return f(*xs)
    nc = n // chunk

    def body(acc, i):
        part = f(*(jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk)
                   for x in xs))
        return jax.tree.map(jnp.add, acc, part), None

    zero = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        jax.eval_shape(f, *(x[:chunk] for x in xs)))
    out, _ = jax.lax.scan(body, zero, jnp.arange(nc))
    if n % chunk:
        out = jax.tree.map(jnp.add, out, f(*(x[nc * chunk:] for x in xs)))
    return out
