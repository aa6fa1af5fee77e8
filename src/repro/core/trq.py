"""Tiered Residual Quantization — the paper's top-level artifact.

Encodes a database against its coarse (PQ) reconstructions into L stacked
ternary levels + per-record scalars, lays the codes out for far memory
(packed base-3), and answers progressive distance queries.

Level stacking: level ℓ encodes the residual left after projecting out the
previous level's approximation (``reconstruct`` in ternary.py), so estimates
tighten monotonically in expectation and the format is "naturally stackable"
(§III-A).  The paper's operating point is L=1 (second-order estimation).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import calibration as calib
from repro.core import packing
from repro.core.decomposition import RecordScalars, compute_scalars
from repro.core.estimator import (ProgressiveState, cauchy_margin,
                                  refine_level, residual_ip_estimate,
                                  topk_threshold)
from repro.core.rows import map_rows
from repro.core.ternary import TernaryCode, reconstruct, ternary_encode


@partial(jax.tree_util.register_dataclass,
         data_fields=("packed", "proj", "norm", "rho"), meta_fields=())
@dataclass(frozen=True)
class TRQLevel:
    """One far-memory level: packed codes + per-level scalars (all device
    arrays; (N, G) uint8 and (N,) f32)."""

    packed: jax.Array       # (N, ceil(D/5)) uint8 — far-memory resident
    proj: jax.Array         # (N,) ⟨δ_ℓ, e_code⟩ = ||δ_ℓ||·rho_ℓ
    norm: jax.Array         # (N,) ||δ_ℓ||
    rho: jax.Array          # (N,) ⟨e_δℓ, e_code⟩


@partial(jax.tree_util.register_dataclass,
         data_fields=("levels", "scalars", "model"), meta_fields=("dim",))
@dataclass(frozen=True)
class TRQCodes:
    """Full FaTRQ encoding of a database."""

    dim: int
    levels: tuple[TRQLevel, ...]
    scalars: RecordScalars          # level-0 metadata: ||δ||², ⟨x_c,δ⟩, rho, ||δ||
    model: calib.CalibrationModel   # calibrated estimator weights

    @property
    def num_levels(self) -> int:
        return len(self.levels)

    def bytes_per_record(self, *, paper_layout: bool = True) -> int:
        """Far-memory footprint. paper_layout: 2 scalars (8 B) + packed code
        per level; otherwise include rho (+4 B/level) for provable bounds."""
        per_level = packing.packed_size(self.dim)
        scalars = 8 if paper_layout else 12
        return self.num_levels * per_level + scalars


def encode_database(x: jax.Array, x_c: jax.Array, *, num_levels: int = 1
                    ) -> tuple[TRQCodes, list[TernaryCode]]:
    """Encode records ``x (N, D)`` against coarse reconstructions ``x_c``.

    Returns the packed TRQCodes (with an identity calibration model — call
    ``calibrate`` to fit) and the raw per-level TernaryCodes (test hooks).
    Every quantity is per-row, so rows are encoded in chunks (the (N, D)
    sort and residual temporaries exist for one chunk only).
    """
    levels, raw, scalars = _encode_rows(x, x_c, num_levels=num_levels)
    codes = TRQCodes(dim=x.shape[-1], levels=tuple(levels), scalars=scalars,
                     model=calib.identity_model())
    return codes, list(raw)


@partial(jax.jit, static_argnames=("num_levels",))
def _encode_rows(x: jax.Array, x_c: jax.Array, *, num_levels: int):
    def rows(xb, xcb):
        delta = xb - xcb
        levels: list[TRQLevel] = []
        raw: list[TernaryCode] = []
        resid = delta
        for _ in range(num_levels):
            tc = ternary_encode(resid)
            raw.append(tc)
            levels.append(TRQLevel(
                packed=packing.pack_ternary(tc.code),
                proj=(tc.norm * tc.rho).astype(jnp.float32),
                norm=tc.norm,
                rho=tc.rho,
            ))
            resid = resid - reconstruct(tc)
        return levels, raw, compute_scalars(xb, xcb, rho=raw[0].rho)

    return map_rows(rows, x, x_c)


def encode_rows(x_new: jax.Array, x_c_new: jax.Array, *, num_levels: int = 1,
                model: calib.CalibrationModel | None = None) -> TRQCodes:
    """Incremental encode: TRQ codes for ``x_new`` (B, D) ONLY.

    Every per-record quantity (``ternary_encode`` trits, level scalars,
    ``compute_scalars``) is row-independent, so encoding a batch of new
    rows in isolation is bit-identical to what a full ``encode_database``
    over the grown database would produce for those rows — the streaming
    subsystem (anns/streaming.py) appends the result with ``write_rows``
    without touching existing rows.  ``model`` carries the already-fitted
    calibration over (calibration is a property of the quantizers, not of
    individual rows; default: identity).
    """
    codes, _ = encode_database(x_new, x_c_new, num_levels=num_levels)
    if model is not None:
        codes = TRQCodes(dim=codes.dim, levels=codes.levels,
                         scalars=codes.scalars, model=model)
    return codes


def write_rows(dst: TRQCodes, src: TRQCodes, start: int) -> TRQCodes:
    """Write ``src``'s rows into ``dst`` at ``start`` (functional append).

    Applies ``lax.dynamic_update_slice`` to every per-record leaf (packed
    codes + level scalars + record scalars); the calibration model and dim
    come from ``dst``.  ``dst`` must have capacity ≥ start + len(src) —
    the streaming row store over-allocates and grows host-side.
    """
    if dst.num_levels != src.num_levels or dst.dim != src.dim:
        raise ValueError("write_rows: level/dim mismatch between dst and src")

    def upd(d, s):
        return jax.lax.dynamic_update_slice(
            d, s.astype(d.dtype), (start,) + (0,) * (d.ndim - 1))

    levels = tuple(jax.tree.map(upd, dl, sl)
                   for dl, sl in zip(dst.levels, src.levels))
    scalars = jax.tree.map(upd, dst.scalars, src.scalars)
    return TRQCodes(dim=dst.dim, levels=levels, scalars=scalars,
                    model=dst.model)


def gather_rows(codes: TRQCodes, idx: jax.Array) -> TRQCodes:
    """Row-gather every per-record leaf (packed codes, level scalars,
    record scalars) at ``idx``; dim + calibration model pass through.
    Compaction/snapshotting in the streaming subsystem moves packed codes
    with this — codes are centroid-relative, so moving a row never needs a
    re-encode."""
    g = lambda a: a[idx]                                      # noqa: E731
    return TRQCodes(dim=codes.dim,
                    levels=tuple(jax.tree.map(g, lv) for lv in codes.levels),
                    scalars=jax.tree.map(g, codes.scalars),
                    model=codes.model)


def unpack_level(codes: TRQCodes, level: int, idx: jax.Array | None = None
                 ) -> jax.Array:
    """Materialize int8 trits for (a subset of) records at one level."""
    packed = codes.levels[level].packed
    if idx is not None:
        packed = packed[idx]
    return packing.unpack_ternary(packed, codes.dim)


def estimate_q_dot_delta(q: jax.Array, codes: TRQCodes,
                         idx: jax.Array | None = None,
                         *, through_level: int | None = None) -> jax.Array:
    """Σ_ℓ ⟨δ,e_cℓ⟩·⟨q,e_cℓ⟩ — the stacked estimate of ⟨q, δ⟩.

    Each level contributes its projection coefficient times the query
    alignment with its code direction; exact as L→D.
    """
    through = codes.num_levels if through_level is None else through_level
    total = 0.0
    for lv in range(through):
        level = codes.levels[lv]
        trits = unpack_level(codes, lv, idx)
        from repro.core.ternary import ternary_inner
        align = ternary_inner(trits, q)           # ⟨q, e_code⟩ (already /√k)
        proj = level.proj if idx is None else level.proj[idx]
        total = total + proj * align
    return total


def calibrate(codes: TRQCodes, q_samples: jax.Array, x: jax.Array,
              x_c: jax.Array, pair_idx: jax.Array) -> TRQCodes:
    """Fit the OLS calibration model on (query, neighbor) pairs.

    q_samples (P, D): calibration queries; pair_idx (P,): the database row
    each query is paired with (index-adjacent neighbors, §III-E — same
    inverted list / graph neighbors; no exact kNN required).
    """
    xi = x[pair_idx]
    xci = x_c[pair_idx]
    d0 = jnp.sum((q_samples - xci) ** 2, axis=-1)
    true_d = jnp.sum((q_samples - xi) ** 2, axis=-1)

    sc = codes.scalars
    delta_sq = sc.delta_sq[pair_idx]
    cross = sc.cross[pair_idx]
    norms = sc.norm[pair_idx]
    rho = sc.rho[pair_idx]

    trits = unpack_level(codes, 0, pair_idx)
    d_ip = jax.vmap(
        lambda qq, cc, nn, rr: residual_ip_estimate(qq, cc[None], nn[None],
                                                    rr[None])[0]
    )(q_samples, trits, norms, rho)

    feats = calib.build_features(d0, d_ip, delta_sq, cross)
    model = calib.fit(feats, true_d)
    return TRQCodes(dim=codes.dim, levels=codes.levels, scalars=codes.scalars,
                    model=model)


def progressive_search(q: jax.Array, d0: jax.Array, codes: TRQCodes,
                       cand_idx: jax.Array, *, k: int,
                       bound: str = "cauchy", z: float = 3.0,
                       axis_name: str | None = None,
                       collect_level_alive: bool = False):
    """Run all TRQ levels over a candidate list for one query, pruning
    between levels.  Returns the final ProgressiveState (estimates + alive
    mask); the pipeline layer turns `alive` into SSD fetches.

    ``axis_name``: inside ``shard_map``, compute every pruning threshold
    globally across the named mesh axis (see ``estimator.topk_threshold``)
    so per-shard survivor masks match an unsharded run exactly.
    ``collect_level_alive``: also return the tuple of alive masks after each
    level — level ℓ+1's far-memory traffic is charged to survivors of level
    ℓ, so the executor needs the whole chain, not just the final mask.
    """
    sc = codes.scalars
    scalars = RecordScalars(delta_sq=sc.delta_sq[cand_idx],
                            cross=sc.cross[cand_idx],
                            rho=sc.rho[cand_idx],
                            norm=sc.norm[cand_idx])
    state = None
    alive = jnp.ones(cand_idx.shape, bool)
    # Level 0 (paper's second-order estimate), then deeper levels tighten.
    trits = unpack_level(codes, 0, cand_idx)
    state = refine_level(q, d0, scalars, trits, codes.model, k=k,
                         bound=bound, z=z, prev_alive=alive,
                         axis_name=axis_name)
    level_alive = [state.alive]
    if codes.num_levels > 1:
        # Deeper levels: each adds −2·⟨q, δ̂_ℓ⟩ with δ̂_ℓ = proj_ℓ·e_code_ℓ,
        # and the certified margin shrinks to the norm of what remains.
        from repro.core.ternary import ternary_inner
        qn = jnp.linalg.norm(q)
        est = state.est
        for lv in range(1, codes.num_levels):
            level = codes.levels[lv]
            trits = unpack_level(codes, lv, cand_idx)
            align = ternary_inner(trits, q)               # ⟨q, e_code_ℓ⟩
            est = est - 2.0 * level.proj[cand_idx] * align
            # remaining residual after level ℓ: ||δ_ℓ||·sqrt(1 − rho_ℓ²)
            rem = level.norm[cand_idx] * jnp.sqrt(
                jnp.clip(1.0 - level.rho[cand_idx] ** 2, 0.0, 1.0))
            margin = 2.0 * qn * rem + codes.model.resid_std
            hi = est + margin
            tau = topk_threshold(hi, state.alive, k, axis_name)
            alive = state.alive & (est - margin <= tau)
            state = ProgressiveState(est=est, lo=est - margin,
                                     alive=alive, tau=tau)
            level_alive.append(alive)
    if collect_level_alive:
        return state, tuple(level_alive)
    return state
