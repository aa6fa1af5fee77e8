"""jit'd public wrappers around the Pallas kernels.

Handle padding to block multiples, build the query digit planes / parameter
vectors, and enforce the per-step VMEM budget.  Backend dispatch (compiled
on TPU, interpreter elsewhere) happens when a kernel is traced, through
its ``interpret=None`` default.  The wrappers take the same logical
arguments as the pure-jnp oracles in ref.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.pq_adc import pq_adc_batch
from repro.kernels.ternary_refine import (VMEM_BUDGET_BYTES, ternary_refine,
                                          ternary_refine_batch,
                                          ternary_refine_fused,
                                          ternary_refine_fused_bounds)


class VMEMBudgetError(ValueError):
    """A block_c / level-count combination exceeds the per-step VMEM budget."""


def _check_vmem_budget(*, what: str, block_c: int, g: int, c_pad: int,
                       num_levels: int = 1, fused: bool = False) -> None:
    """Reject block/level configurations whose per-step working set cannot
    fit in VMEM.  Counted per grid step: double-buffered input blocks
    (codes + scalars + level scalars + digit planes + params) and the
    unpacked-code temporaries, plus, for the fused kernels, the
    full-candidate-set scratch (est/lo/hi/alive/delta), the resident
    (C/BC, BC) output blocks (double-buffered; at most three) and the
    threshold pass's full-candidate-set temporaries."""
    per_step = (block_c * g                # packed codes (uint8)
                + block_c * 8 * 4          # level-0 scalars
                + 5 * g * 4                # query digit planes
                + 8 * 4)                   # params
    if fused:
        per_step += block_c * 4 * 4        # level scalars
    total = 2 * per_step                   # double buffering
    total += 4 * block_c * g * 4           # unpacked bytes, digits, acc
    if fused:
        total += 5 * c_pad * 4             # est/lo/hi/alive/delta scratch
        total += 3 * 2 * c_pad * 4         # resident output blocks
        total += 6 * c_pad * 4             # threshold-pass temporaries
    if total > VMEM_BUDGET_BYTES:
        raise VMEMBudgetError(
            f"{what}: block_c={block_c} x {num_levels} level(s) over "
            f"{c_pad} padded candidates needs ~{total / 2**20:.1f} MiB of "
            f"VMEM per grid step, over the {VMEM_BUDGET_BYTES / 2**20:.0f} "
            f"MiB per-core budget; lower block_c or the refine budget")


def _pad_rows(x: jax.Array, mult: int) -> tuple[jax.Array, int]:
    c = x.shape[0]
    pad = (-c) % mult
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x, c


def _pad_axis(x: jax.Array, axis: int, mult: int) -> tuple[jax.Array, int]:
    c = x.shape[axis]
    pad = (-c) % mult
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x, c


def _pad_axis1(x: jax.Array, mult: int) -> tuple[jax.Array, int]:
    return _pad_axis(x, 1, mult)


@functools.partial(jax.jit, static_argnames=("block_c",))
def refine_scores(packed: jax.Array, q: jax.Array, d0: jax.Array,
                  delta_sq: jax.Array, cross: jax.Array, norm: jax.Array,
                  rho: jax.Array, w: jax.Array, bias: jax.Array,
                  *, block_c: int = 512) -> jax.Array:
    """Fused refine over a candidate batch → (C, 3) [est, est_raw, margin].

    Drop-in accelerated form of core.estimator.refine_level's math.
    """
    c, g = packed.shape
    q_planes = ref.make_query_planes(q.astype(jnp.float32), g)
    scalars = jnp.stack([d0, delta_sq, cross, norm, rho] +
                        [jnp.zeros_like(d0)] * 3, axis=-1)  # (C, 8)
    qn = jnp.linalg.norm(q)
    params = jnp.concatenate([qn[None], w.astype(jnp.float32),
                              bias[None].astype(jnp.float32),
                              jnp.zeros((2,), jnp.float32)])[None, :]  # (1,8)
    packed_p, c0 = _pad_rows(packed, block_c)
    scalars_p, _ = _pad_rows(scalars.astype(jnp.float32), block_c)
    _check_vmem_budget(what="refine_scores", block_c=block_c, g=g,
                       c_pad=packed_p.shape[0])
    out = ternary_refine(packed_p, q_planes, scalars_p, params,
                         block_c=block_c)
    return out[:c0]


def _batch_planes_params(q: jax.Array, g: int, w: jax.Array,
                         bias: jax.Array, extra: jax.Array | None = None
                         ) -> tuple[jax.Array, jax.Array]:
    """Per-query digit planes (Q, 5, G) + params (Q, 8)
    [qn, w0..w3, bias, extra0, extra1]."""
    q32 = q.astype(jnp.float32)
    nq = q32.shape[0]
    q_planes = jax.vmap(lambda qq: ref.make_query_planes(qq, g))(q32)
    qn = jnp.linalg.norm(q32, axis=-1)                          # (Q,)
    wb = jnp.concatenate([w.astype(jnp.float32),
                          bias[None].astype(jnp.float32)])
    params = jnp.concatenate(
        [qn[:, None], jnp.broadcast_to(wb, (nq, 5)),
         jnp.zeros((nq, 2), jnp.float32) if extra is None
         else jnp.broadcast_to(extra, (nq, 2))], axis=1)        # (Q, 8)
    return q_planes, params


@functools.partial(jax.jit, static_argnames=("block_c",))
def refine_scores_batch(packed: jax.Array, q: jax.Array, d0: jax.Array,
                        delta_sq: jax.Array, cross: jax.Array,
                        norm: jax.Array, rho: jax.Array, w: jax.Array,
                        bias: jax.Array, *, block_c: int = 512) -> jax.Array:
    """Fused refine over a query micro-batch → (Q, C, 3).

    packed (Q, C, G) per-query gathered codes, q (Q, D), per-record scalars
    (Q, C); calibration w (4,) + bias are shared across queries.  Same math
    as ``refine_scores`` run once per query, in a single kernel launch.
    """
    nq, c, g = packed.shape
    q_planes, params = _batch_planes_params(q, g, w, bias)
    scalars = jnp.stack([d0, delta_sq, cross, norm, rho] +
                        [jnp.zeros_like(d0)] * 3, axis=-1)     # (Q, C, 8)
    packed_p, c0 = _pad_axis1(packed, block_c)
    scalars_p, _ = _pad_axis1(scalars.astype(jnp.float32), block_c)
    _check_vmem_budget(what="refine_scores_batch", block_c=block_c, g=g,
                       c_pad=packed_p.shape[1])
    out = ternary_refine_batch(packed_p, q_planes, scalars_p, params,
                               block_c=block_c)
    return out[:, :c0]


def _fused_inputs(packed_levels, q, d0, delta_sq, cross, norm, rho, valid,
                  is_delta, lvl_proj, lvl_norm, lvl_rho, w, bias, resid_std,
                  z, block_c):
    """Shared input assembly for the fused kernels: stack the level-0
    scalar planes (valid + is_delta flags in rows 5/6), the per-level
    [proj, norm, rho] planes, and the per-query params with
    [z·resid_std, resid_std] in the extra slots; lay candidates along the
    minor axis (the kernels' lane-major layout) and pad them to a block_c
    multiple (padded slots have valid=0, so they never survive)."""
    l, nq, c, g = packed_levels.shape
    rs = jnp.asarray(resid_std, jnp.float32)
    extra = jnp.stack([jnp.float32(z) * rs, rs])
    q_planes, params = _batch_planes_params(q, g, w, bias, extra)
    zeros = jnp.zeros_like(d0)
    scalars = jnp.stack(
        [d0, delta_sq, cross, norm, rho, valid.astype(jnp.float32),
         is_delta.astype(jnp.float32), zeros], axis=1)          # (Q, 8, C)
    level_scalars = jnp.stack(
        [lvl_proj, lvl_norm, lvl_rho, jnp.zeros_like(lvl_proj)],
        axis=2)                                                 # (L, Q, 4, C)
    packed_p, c0 = _pad_axis(jnp.swapaxes(packed_levels, 2, 3), 3, block_c)
    scalars_p, _ = _pad_axis(scalars.astype(jnp.float32), 2, block_c)
    lvl_p, _ = _pad_axis(level_scalars.astype(jnp.float32), 3, block_c)
    return (packed_p, jnp.swapaxes(q_planes, 1, 2), scalars_p, lvl_p, params,
            c0)


@functools.partial(jax.jit, static_argnames=("k", "bound", "block_c"))
def fused_refine_scores_batch(packed_levels: jax.Array, q: jax.Array,
                              d0: jax.Array, delta_sq: jax.Array,
                              cross: jax.Array, norm: jax.Array,
                              rho: jax.Array, valid: jax.Array,
                              is_delta: jax.Array, lvl_proj: jax.Array,
                              lvl_norm: jax.Array, lvl_rho: jax.Array,
                              w: jax.Array, bias: jax.Array,
                              resid_std: jax.Array, z: float, *, k: int,
                              bound: str, block_c: int = 512
                              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Whole progressive-refinement loop in ONE kernel launch.

    packed_levels (L, Q, C, G) per-level gathered codes; q (Q, D);
    level-0 scalars d0/delta_sq/cross/norm/rho + masks valid/is_delta all
    (Q, C); per-level lvl_proj/lvl_norm/lvl_rho (L, Q, C); calibration
    w (4,)/bias; resid_std + quantile width z for the certified margins.

    Returns (est (Q, C), alive (Q, C) bool, counts (Q, 2L) int32) — counts
    rows are [survivors after level 0..L−1, then the delta-page survivor
    split for the ledger].  Thresholds are computed on-chip, so this form
    is for unsharded execution; sharded callers use
    ``fused_refine_bounds_batch`` and pool thresholds across the mesh.
    """
    inputs = _fused_inputs(packed_levels, q, d0, delta_sq, cross, norm, rho,
                           valid, is_delta, lvl_proj, lvl_norm, lvl_rho, w,
                           bias, resid_std, z, block_c)
    packed_p, q_planes, scalars_p, lvl_p, params, c0 = inputs
    l, g = packed_levels.shape[0], packed_levels.shape[3]
    _check_vmem_budget(what="fused_refine_scores_batch", block_c=block_c,
                       g=g, c_pad=packed_p.shape[3], num_levels=l,
                       fused=True)
    est, alive, counts = ternary_refine_fused(
        packed_p, q_planes, scalars_p, lvl_p, params, k=k, bound=bound,
        block_c=block_c)
    return est[:, :c0], alive[:, :c0].astype(bool), counts


@functools.partial(jax.jit, static_argnames=("bound", "block_c"))
def fused_refine_bounds_batch(packed_levels: jax.Array, q: jax.Array,
                              d0: jax.Array, delta_sq: jax.Array,
                              cross: jax.Array, norm: jax.Array,
                              rho: jax.Array, valid: jax.Array,
                              is_delta: jax.Array, lvl_proj: jax.Array,
                              lvl_norm: jax.Array, lvl_rho: jax.Array,
                              w: jax.Array, bias: jax.Array,
                              resid_std: jax.Array, z: float, *, bound: str,
                              block_c: int = 512
                              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sharded companion of ``fused_refine_scores_batch``: identical inputs
    and single-launch level stacking, returning (est (Q, C), lo (Q, L, C),
    hi (Q, L, C)) so the caller can exchange pruning thresholds globally
    (``pooled_k_smallest`` over the mesh axis) between level segments."""
    inputs = _fused_inputs(packed_levels, q, d0, delta_sq, cross, norm, rho,
                           valid, is_delta, lvl_proj, lvl_norm, lvl_rho, w,
                           bias, resid_std, z, block_c)
    packed_p, q_planes, scalars_p, lvl_p, params, c0 = inputs
    l, g = packed_levels.shape[0], packed_levels.shape[3]
    _check_vmem_budget(what="fused_refine_bounds_batch", block_c=block_c,
                       g=g, c_pad=packed_p.shape[3], num_levels=l,
                       fused=True)
    est, lo, hi = ternary_refine_fused_bounds(
        packed_p, q_planes, scalars_p, lvl_p, params, bound=bound,
        block_c=block_c)
    return est[:, :c0], lo[:, :, :c0], hi[:, :, :c0]


def adc_scores(codes: jax.Array, lut: jax.Array) -> jax.Array:
    """PQ-ADC distances of one query's candidates: codes (C, M) uint8,
    lut (M, K) → (C,), by the batched kernel at Q = 1."""
    return pq_adc_batch(codes[None], lut[None])[0]
