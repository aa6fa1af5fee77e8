"""Generator ``clustered_unit``: unit-norm corpus and in-distribution
queries, made on the device.

The statistics are those of the system's own synthetic generator: unit-norm
embeddings around ``clusters`` random unit centres, with an anisotropic
spread whose per-dimension scale decays geometrically (by ``decay`` every
``decay_dims`` dimensions), and queries drawn near base rows with Gaussian
noise of norm ``query_noise``.  Unlike that generator, every row and every
query is a pure function of a seed and its index: a query can be made
without the corpus on the device, and the reference can make the corpus
again after the system under test has been freed.

The corpus and its pool of queries come from the configuration's own
``corpus_seed``, as a benchmark's data set and test queries are fixed; the
run's ``--seed`` orders the requests (``loadgen.query_indices``).  So
every run builds the same index and asks the same set of questions, and
the seed changes their order, not the shapes the system compiles or the
work it does.  ``run.py --corpus-seed`` builds on another corpus and pool,
for runs that prove ``correct`` beyond the one data set.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.common import block_rows as _block_rows
from chipbench.common import key_data as _key_data
from chipbench.common import seed_key


class Spec:
    """What a configuration file says about its data."""

    def __init__(self, cfg: dict):
        d = cfg["data"]
        self.rows = int(cfg["rows"])
        self.dim = int(cfg["dim"])
        self.corpus_seed = int(d["corpus_seed"])
        self.clusters = int(d["clusters"])
        self.spread = float(d["spread"])
        self.decay = float(d["decay"])
        self.decay_dims = float(d["decay_dims"])
        self.query_noise = float(d["query_noise"])

    def static(self) -> tuple:
        return (self.rows, self.dim, self.clusters, self.spread, self.decay,
                self.decay_dims, self.query_noise)


def _rows(keys, idx, spec: tuple) -> jax.Array:
    """Rows ``idx`` (B,) of the corpus → (B, dim) float32, unit norm.
    keys: centres, cluster ids, row noise."""
    _, dim, clusters, spread, decay, decay_dims, _ = spec
    centres = jax.random.normal(keys[0], (clusters, dim))
    centres = centres / jnp.linalg.norm(centres, axis=-1, keepdims=True)
    scales = decay ** (jnp.arange(dim) / decay_dims)

    def one(i):
        cid = jax.random.randint(jax.random.fold_in(keys[1], i), (), 0,
                                 clusters)
        noise = jax.random.normal(jax.random.fold_in(keys[2], i), (dim,))
        return centres[cid] + noise * scales * spread

    x = jax.vmap(one)(idx)
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("spec",))
def _corpus(corpus_keys, spec: tuple) -> jax.Array:
    keys = jax.random.wrap_key_data(corpus_keys)
    rows, dim = spec[0], spec[1]
    b = _block_rows(rows)
    blocks = jax.lax.map(
        lambda j: _rows(keys, j * b + jnp.arange(b), spec),
        jnp.arange(rows // b))
    return blocks.reshape(rows, dim)


def corpus(spec: Spec) -> jax.Array:
    """The whole corpus (rows, dim) float32, in one jitted call."""
    return _corpus(_key_data(spec.corpus_seed, 7)[:3], spec.static())


def build_key(spec: Spec) -> jax.Array:
    """The raw key the index is built with: fixed with the corpus."""
    return jax.random.key_data(seed_key(spec.corpus_seed, stream=1))


@functools.partial(jax.jit, static_argnames=("spec",))
def _queries(corpus_keys, query_keys, idx, spec: tuple) -> jax.Array:
    keys = jax.random.wrap_key_data(corpus_keys)
    pick_key, noise_key = jax.random.wrap_key_data(query_keys)
    rows, dim, noise = spec[0], spec[1], spec[-1]
    pick = jax.vmap(lambda i: jax.random.randint(
        jax.random.fold_in(pick_key, i), (), 0, rows))(idx)
    eps = jax.vmap(lambda i: jax.random.normal(
        jax.random.fold_in(noise_key, i), (dim,)))(idx)
    q = _rows(keys, pick, spec) + noise * eps / jnp.sqrt(dim)
    return q / jnp.linalg.norm(q, axis=-1, keepdims=True)


def queries(spec: Spec, idx, *, warmup: bool = False) -> jax.Array:
    """Queries number ``idx`` of the configuration's pool → (len(idx), dim).
    The warm-up pool is disjoint from the measured one."""
    keys = _key_data(spec.corpus_seed, 7)
    return _queries(keys[:3], keys[5:] if warmup else keys[3:5],
                    jnp.asarray(idx, jnp.int32), spec.static())
