"""Offline linear calibration of the refinement estimator (FaTRQ §III-E).

Recall is decided by ranking *near the top-k boundary*, not by global MSE.
FaTRQ fits ``Ŵ = argmin_W ||D − A W||²`` by OLS on a small calibration set
(~0.3% of records), where per (query, record) pair

    A = [ d̂₀,  d̂_ip,  ||δ||²,  ⟨x_c, δ⟩ ]

with d̂_ip the ternary estimate of −2⟨q, δ⟩ and D the true squared distance.
Calibration pairs come from the index itself (same inverted list for IVF,
graph neighbors for CAGRA) — no exact kNN needed.

With an exact residual inner product the identity weights are
``W* = [1, 1, 1, 2]`` (see decomposition.py), so the learned W also absorbs
the systematic shrinkage E[⟨e_code, e_δ⟩] of the ternary estimate.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class CalibrationModel(NamedTuple):
    w: jax.Array          # (F,) or (F+1,) with bias
    bias: jax.Array       # scalar
    resid_std: jax.Array  # scalar — std of OLS residuals, used as the
                          # calibrated pruning margin (quantile bound).


def build_features(d0: jax.Array, d_ip: jax.Array, delta_sq: jax.Array,
                   cross: jax.Array) -> jax.Array:
    """Stack the paper's 4 features on a new trailing axis."""
    return jnp.stack([d0, d_ip, delta_sq, cross], axis=-1)


def fit(features: jax.Array, target: jax.Array, *, ridge: float = 1e-6
        ) -> CalibrationModel:
    """OLS (tiny ridge for conditioning) with intercept. features (N,F).
    Runs at HIGHEST matmul precision, so a TPU fits the same model as a
    CPU (its default f32 matmul rounds through bf16)."""
    n = features.shape[0]
    a = jnp.concatenate([features, jnp.ones((n, 1), features.dtype)], axis=1)
    with jax.default_matmul_precision("highest"):
        gram = a.T @ a + ridge * jnp.eye(a.shape[1], dtype=a.dtype)
        coef = jnp.linalg.solve(gram, a.T @ target)
        pred = a @ coef
    resid_std = jnp.std(target - pred)
    return CalibrationModel(w=coef[:-1], bias=coef[-1], resid_std=resid_std)


def predict(model: CalibrationModel, features: jax.Array) -> jax.Array:
    """A·Ŵ + b — the lightweight query-time computation."""
    return features @ model.w + model.bias


def identity_model(dtype=jnp.float32) -> CalibrationModel:
    """W* = [1,1,1,2], b=0 — exact when d̂_ip is exact (test invariant)."""
    return CalibrationModel(w=jnp.asarray([1.0, 1.0, 1.0, 2.0], dtype),
                            bias=jnp.asarray(0.0, dtype),
                            resid_std=jnp.asarray(0.0, dtype))
