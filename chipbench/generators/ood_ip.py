"""Generator ``ood_ip``: rows of unequal norm ranked by inner product, and
queries drawn from another distribution than the rows, made on the device.

The NeurIPS'23 big-ann-benchmarks OOD track (Yandex Text-to-Image) ranks
image embeddings by their inner product with text embeddings of another
model.  No public source gives that data's spectrum or norm spread, so
every parameter is the configuration's (``data`` block), and a
configuration that uses this generator lists them under ``assumed``:

* rows ("image"): ``clusters`` random unit centres with an anisotropic
  spread whose per-dimension scale decays by ``decay`` every
  ``decay_dims`` dimensions (as ``clustered_unit``), each row then scaled
  to a norm drawn log-normal with ``norm_sigma`` (median 1): rows are not
  normalised;
* queries ("text"): the direction of a base row picked from the seed,
  plus isotropic noise of norm ``query_noise``, through one fixed seeded
  near-identity map ``I + map_eps·G/√dim`` (G standard normal) and a
  common shift of norm ``shift`` along one fixed seeded direction, each
  query then scaled to its own log-normal norm (``query_norm_sigma``).

Every row and every query is a pure function of ``corpus_seed`` and its
index, as in ``clustered_unit``: the corpus can be made again after the
system under test has been freed, and the warm-up pool of queries is
disjoint from the measured one.  ``DEFAULTS`` is a ``data`` block that
gives the properties the tests pin: the exact L2 top-10 holds at most half
of the exact inner-product top-10, and under the rows' mean and covariance
the queries' median Mahalanobis distance is at least 1.5 times that of
rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.common import block_rows, key_data, seed_key

DEFAULTS = {"corpus_seed": 0, "clusters": 64, "spread": 0.35, "decay": 0.7,
            "decay_dims": 16, "norm_sigma": 0.3, "query_noise": 0.5,
            "map_eps": 0.5, "shift": 0.3, "query_norm_sigma": 0.2}

# keys split from corpus_seed: rows (centres, cluster ids, row noise, row
# norms), the queries' map and shift, and each pool's picks, noise, norms
N_KEYS = 12
ROW_KEYS, MAP_KEYS, POOL_KEYS, WARM_KEYS = (slice(0, 4), slice(4, 6),
                                             slice(6, 9), slice(9, 12))


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
        self.norm_sigma = float(d["norm_sigma"])
        self.query_noise = float(d["query_noise"])
        self.map_eps = float(d["map_eps"])
        self.shift = float(d["shift"])
        self.query_norm_sigma = float(d["query_norm_sigma"])

    def static(self) -> tuple:
        return (self.rows, self.dim, self.clusters, self.spread, self.decay,
                self.decay_dims, self.norm_sigma, self.query_noise,
                self.map_eps, self.shift, self.query_norm_sigma)


def _directions(keys, idx, spec: tuple) -> jax.Array:
    """Unit directions of rows ``idx`` (B,) → (B, dim) float32."""
    _, dim, clusters, spread, decay, decay_dims = spec[:6]
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


def _log_normal(key, idx, sigma: float) -> jax.Array:
    """One log-normal factor (median 1) per index → (B, 1)."""
    z = jax.vmap(lambda i: jax.random.normal(jax.random.fold_in(key, i)))(
        idx)
    return jnp.exp(sigma * z)[:, None]


def _rows(keys, idx, spec: tuple) -> jax.Array:
    """Rows ``idx`` (B,) of the corpus → (B, dim) float32, each with its
    own norm.  keys: centres, cluster ids, row noise, row norms."""
    return _directions(keys, idx, spec) * _log_normal(keys[3], idx, spec[6])


@functools.partial(jax.jit, static_argnames=("spec",))
def _corpus(row_keys, spec: tuple) -> jax.Array:
    keys = jax.random.wrap_key_data(row_keys)
    rows, dim = spec[0], spec[1]
    b = block_rows(rows)

    def fill(j, x):      # each block written in place: no second copy
        return jax.lax.dynamic_update_slice_in_dim(
            x, _rows(keys, j * b + jnp.arange(b), spec), j * b, axis=0)

    return jax.lax.fori_loop(0, rows // b, fill,
                             jnp.zeros((rows, dim), jnp.float32))


def corpus(spec: Spec) -> jax.Array:
    """The whole corpus (rows, dim) float32, in one jitted call."""
    return _corpus(key_data(spec.corpus_seed, N_KEYS)[ROW_KEYS],
                   spec.static())


def build_key(spec: Spec) -> jax.Array:
    """The raw key the index is built with: fixed with the corpus."""
    return jax.random.key_data(seed_key(spec.corpus_seed, stream=1))


@functools.partial(jax.jit, static_argnames=("spec",))
def _queries(row_keys, map_keys, pool_keys, idx, spec: tuple) -> jax.Array:
    keys = jax.random.wrap_key_data(row_keys)
    map_key, shift_key = jax.random.wrap_key_data(map_keys)
    pick_key, noise_key, norm_key = jax.random.wrap_key_data(pool_keys)
    rows, dim = spec[0], spec[1]
    query_noise, map_eps, shift, query_norm_sigma = spec[7:]
    pick = jax.vmap(lambda i: jax.random.randint(
        jax.random.fold_in(pick_key, i), (), 0, rows))(idx)
    eps = jax.vmap(lambda i: jax.random.normal(
        jax.random.fold_in(noise_key, i), (dim,)))(idx)
    t = _directions(keys, pick, spec) + query_noise * eps / jnp.sqrt(dim)
    a = jnp.eye(dim) + map_eps * jax.random.normal(map_key, (dim, dim)) \
        / jnp.sqrt(dim)
    s = jax.random.normal(shift_key, (dim,))
    u = jnp.matmul(t, a, precision=jax.lax.Precision.HIGHEST) \
        + shift * s / jnp.linalg.norm(s)
    u = u / jnp.linalg.norm(u, axis=-1, keepdims=True)
    return u * _log_normal(norm_key, idx, query_norm_sigma)


def queries(spec: Spec, idx, *, warmup: bool = False) -> jax.Array:
    """Queries number ``idx`` of the configuration's pool → (len(idx), dim).
    The warm-up pool is disjoint from the measured one."""
    keys = key_data(spec.corpus_seed, N_KEYS)
    return _queries(keys[ROW_KEYS], keys[MAP_KEYS],
                    keys[WARM_KEYS] if warmup else keys[POOL_KEYS],
                    jnp.asarray(idx, jnp.int32), spec.static())
