"""Fused FaTRQ refinement Pallas kernels — the paper's CXL accelerator
datapath, re-expressed for the TPU memory hierarchy.

The paper streams packed ternary codes from far memory into a small decoder
LUT + add/sub datapath with per-level early exit.  On TPU the analogous
structure is: packed codes live in HBM at 1.6 bit/dim (the "far" tier),
each grid step DMAs one candidate block into VMEM (the "near" tier), and
the VPU unpacks + scores it without ever materializing full-precision
residuals in HBM.  HBM traffic is ⌈D/5⌉+20 bytes per candidate instead of
4·D for full vectors — the bandwidth form of the paper's "no multiplies".

Three kernels share the digit-plane scoring body:

* ``ternary_refine`` / ``ternary_refine_batch`` — level-0 scoring only:
  unpack → ternary inner product → calibrated estimate → certified margin
  for one candidate block per grid step.
* ``ternary_refine_fused`` — the WHOLE progressive-refinement loop in one
  ``pallas_call``: the grid walks ``(query, level, candidate-block)`` with
  the level segments sequential, the running estimate / certified bounds /
  alive mask resident in VMEM scratch across segments, the per-level
  pruning threshold (kth-smallest upper bound among survivors) computed
  on-chip and carried in SMEM scratch, and per-level survivor counts
  (total + delta-page split) emitted for the cost ledger.  Intermediate
  estimates and masks never round-trip through HBM.
* ``ternary_refine_fused_bounds`` — the sharded variant of the same
  single-launch datapath: level stacking still happens entirely in VMEM
  scratch, but instead of masking on-chip it emits each level's certified
  ``(lo, hi)`` interval so the caller can pool pruning thresholds globally
  across a mesh axis (``shard_map`` collectives cannot run inside a
  kernel); the alive chain applied outside is arithmetically identical.

Layout note: base-3 digit i of byte g holds dim 5g+i, so the query is
pre-arranged into 5 digit planes of (G,) (see ref.make_query_planes) and
unpacking is 5 div/mod passes over the byte block — no reshapes, no
gathers, fully vectorized on 8×128 VPU tiles.  The fused kernels take
their candidates along lanes ((G, C) codes, (rows, C) scalar planes), the
layout the TPU compiler tiles without padding or relayout.

``interpret=None`` picks the mode when the kernel is traced (compiled on
TPU, interpreter elsewhere); pass an explicit bool only to force a mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_POW3 = (1, 3, 9, 27, 81)


#: Per-core VMEM the kernels budget against: the TPU compiler's default
#: scoped-VMEM limit on v4/v5e (16 MiB).
VMEM_BUDGET_BYTES = 16 * 1024 * 1024


def resolve_interpret(interpret: bool | None) -> bool:
    """None → decided when the kernel is traced: compiled when JAX's
    default backend is a TPU, the interpreter everywhere else.  Nothing
    is asked of the device when this module is imported."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _block_align(y, plane, axis: int):
    """Digit-plane unpack + ternary inner product for one candidate block.

    y int32 packed bytes with the G byte axis at ``axis``; ``plane(i)`` is
    query digit plane i, broadcastable against y → align = Σc·q/√k, the
    ⟨q, e_code⟩ term every level's estimate update consumes, with the G
    axis reduced (kept as size 1).
    """
    acc = jnp.zeros(y.shape, jnp.float32)
    kcnt = jnp.zeros(y.shape, jnp.int32)
    for i in range(5):
        digit = (y // _POW3[i]) % 3 - 1            # ∈ {-1,0,1}
        trit = digit.astype(jnp.float32)
        acc = acc + trit * plane(i)
        kcnt = kcnt + digit * digit
    raw = jnp.sum(acc, axis=axis, keepdims=True)   # Σ c·q
    k = jnp.sum(kcnt, axis=axis, keepdims=True).astype(jnp.float32)  # ||c||²
    return raw / jnp.sqrt(jnp.maximum(k, 1.0))     # Σ c·q / √k


def _score_block(align, rec, params):
    """Shared level-0 scoring math: one candidate block of one query.

    align = Σc·q/√k; rec = (d0, ||δ||², ⟨x_c,δ⟩, ||δ||, rho) planes shaped
    like align; params = (qn, w0..w3, bias, …) scalars → (est, est_raw,
    margin).  Elementwise only, so each kernel lays its candidates out as
    its grid needs.
    """
    qn, w0, w1, w2, w3, bias = params[:6]
    d0, delta_sq, cross, norm, rho = rec

    e_align = align / jnp.maximum(qn, 1e-30)
    d_ip = -2.0 * norm * rho * align
    est = w0 * d0 + w1 * d_ip + w2 * delta_sq + w3 * cross + bias
    est_raw = d0 + delta_sq + 2.0 * cross + d_ip
    margin = (2.0 * qn * norm
              * jnp.sqrt(jnp.clip(1.0 - e_align * e_align, 0.0, 1.0))
              * jnp.sqrt(jnp.clip(1.0 - rho * rho, 0.0, 1.0)))
    return est, est_raw, margin


def _kth_smallest(vals, k: int):
    """kth-smallest VALUE of a 2-D array (the pruning threshold τ).

    Matches ``estimator.pooled_k_smallest`` on the same multiset: the kth
    order statistic is tie-invariant, so masking one occurrence of the
    minimum per round (the lowest flat index holding it) k−1 times and
    taking the remaining min is exactly the value ``lax.top_k`` would
    return.  k is static and small (final_k), so the loop unrolls to
    3(k−1)+1 VPU reductions.
    """
    rows, cols = vals.shape
    flat = (jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0) * cols
            + jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1))
    v = vals
    for _ in range(k - 1):
        m = jnp.min(v, keepdims=True)
        first = jnp.min(jnp.where(v == m, flat, rows * cols), keepdims=True)
        v = jnp.where(flat == first, jnp.inf, v)
    return jnp.min(v)


def _level0_bounds(est, est_raw, margin, params, bound: str):
    """Certified (lo, hi) for level 0 under the configured bound."""
    if bound == "cauchy":
        return est_raw - margin, est_raw + margin
    if bound == "quantile":
        qm = params[6]                             # z · resid_std
        return est - qm, est + qm
    raise ValueError(f"unknown bound {bound!r}")


def _deeper_bounds(est_prev, align, lsc, params):
    """Level-ℓ≥1 stacking for one block: est −= 2·proj·align, certified
    margin 2·||q||·||δ_rem|| + resid_std (what trq.progressive_search
    computes).  lsc = level-ℓ (proj, norm, rho) planes."""
    qn, resid_std = params[0], params[7]
    proj, norm, rho = lsc
    est = est_prev - 2.0 * proj * align
    rem = norm * jnp.sqrt(jnp.clip(1.0 - rho * rho, 0.0, 1.0))
    marg = 2.0 * qn * rem + resid_std
    return est, est - marg, est + marg


# --------------------------------------------------------- level-0 kernels


def _score_rows(y, qplanes, scal, params):
    """Candidate-major level-0 scoring: y (BC, G) bytes, qplanes (5, G),
    scal (BC, 8), params (8,) → (est, est_raw, margin), each (BC, 1)."""
    align = _block_align(y.astype(jnp.int32),
                         lambda i: qplanes[i, :][None, :], axis=1)
    rec = tuple(scal[:, j:j + 1] for j in range(5))
    return _score_block(align, rec, tuple(params[j] for j in range(8)))


def _refine_kernel(packed_ref, qplanes_ref, scal_ref, params_ref, out_ref):
    """One candidate block: (BC, G) bytes → (BC, 3) [est, est_raw, margin]."""
    est, est_raw, margin = _score_rows(packed_ref[...], qplanes_ref[...],
                                       scal_ref[...], params_ref[0])
    out_ref[:, 0:1] = est
    out_ref[:, 1:2] = est_raw
    out_ref[:, 2:3] = margin


def _refine_kernel_batch(packed_ref, qplanes_ref, scal_ref, params_ref,
                         out_ref):
    """Query-batched variant: block shapes carry a leading (1,) query dim.

    Grid is (Q, C/BC); each step scores one candidate block of one query, so
    a whole micro-batch of queries runs as a single kernel launch — the
    executor's batched level-0 datapath (the fully fused multi-level loop
    is ``_fused_kernel`` below).
    """
    est, est_raw, margin = _score_rows(packed_ref[0], qplanes_ref[0],
                                       scal_ref[0], params_ref[0])
    out_ref[0, :, 0:1] = est
    out_ref[0, :, 1:2] = est_raw
    out_ref[0, :, 2:3] = margin


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def ternary_refine_batch(packed: jax.Array, q_planes: jax.Array,
                         scalars: jax.Array, params: jax.Array, *,
                         block_c: int = 512, interpret: bool | None = None
                         ) -> jax.Array:
    """Multi-query level-0 refine: one launch scores Q×C candidates.

    packed (Q, C, G) uint8 — per-query gathered codes; q_planes (Q, 5, G);
    scalars (Q, C, 8) f32 [d0, ||δ||², ⟨x_c,δ⟩, ||δ||, rho, 0…];
    params (Q, 8) f32 [qn, w0..w3, b, 0, 0] (w/b normally shared, qn per
    query) → (Q, C, 3) f32 [est, est_raw, margin].

    C must be a multiple of block_c (ops.py pads).  The grid walks queries
    in the outer dimension so each query's candidate blocks stream through
    VMEM back-to-back with its (5, G) digit planes held resident.
    ``interpret=None`` picks the mode at trace time (compiled on TPU).
    """
    nq, c, g = packed.shape
    assert c % block_c == 0, (c, block_c)
    grid = (nq, c // block_c)
    return pl.pallas_call(
        _refine_kernel_batch,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, g), lambda qi, ci: (qi, ci, 0)),
            pl.BlockSpec((1, 5, g), lambda qi, ci: (qi, 0, 0)),
            pl.BlockSpec((1, block_c, 8), lambda qi, ci: (qi, ci, 0)),
            pl.BlockSpec((1, 8), lambda qi, ci: (qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_c, 4), lambda qi, ci: (qi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, c, 4), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(packed, q_planes, scalars, params)[..., :3]


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def ternary_refine(packed: jax.Array, q_planes: jax.Array, scalars: jax.Array,
                   params: jax.Array, *, block_c: int = 512,
                   interpret: bool | None = None) -> jax.Array:
    """packed (C, G) uint8, q_planes (5, G) f32, scalars (C, 5) f32
    [d0, ||δ||², ⟨x_c,δ⟩, ||δ||, rho], params (1, 8) f32
    [qn, w0..w3, b, 0, 0] → (C, 3) f32.

    C must be a multiple of block_c (ops.py pads).  VMEM per step:
    block_c·G bytes of codes + 5·G query floats + block_c·8 scalars —
    e.g. 512×154 ≈ 77 KiB codes, a small slice of a TPU core's ~16 MiB
    VMEM, so several steps double-buffer (ops.py enforces the budget).
    ``interpret=None`` picks the mode at trace time (compiled on TPU).
    """
    c, g = packed.shape
    assert c % block_c == 0, (c, block_c)
    grid = (c // block_c,)
    return pl.pallas_call(
        _refine_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_c, g), lambda i: (i, 0)),
            pl.BlockSpec((5, g), lambda i: (0, 0)),
            pl.BlockSpec((block_c, 8), lambda i: (i, 0)),
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, 4), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, 4), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(packed, q_planes, scalars, params)[:, :3]


# ------------------------------------------- fused multi-level kernels
#
# Grid (Q, L, C/BC): for each query, the level segments run sequentially
# (TPU grids are sequential on a core), each walking the candidate blocks.
# Candidates lie along lanes: a code block is (G, BC) bytes, every scalar
# plane a (1, BC) row, so block reductions land lane-dense.  The running
# estimate, certified (lo, hi) interval, alive mask and delta-page flag
# live in (C/BC, BC) VMEM scratch that persists across segments (row ci
# holds block ci); per-level thresholds live in SMEM scratch and the
# per-query params are read from SMEM.  Only the FINAL estimate, alive
# mask and per-level survivor counts ever reach HBM.


def _planes(ref, n: int) -> tuple:
    """The first n (1, BC) rows of a (rows, BC) scalar-plane block."""
    return tuple(ref[j:j + 1, :] for j in range(n))


def _align_rows(packed_ref, qplanes_ref):
    """Lane-major ``_block_align``: (G, BC) bytes against (G, 5) planes →
    (1, BC)."""
    qp = qplanes_ref[...]
    return _block_align(packed_ref[...].astype(jnp.int32),
                        lambda i: qp[:, i:i + 1], axis=0)


def _fused_kernel(packed_ref, qplanes_ref, scal0_ref, lvls_ref, params_ref,
                  est_out, alive_out, counts_out,
                  est_s, lo_s, hi_s, alive_s, delta_s, tau_s, *,
                  num_levels: int, n_blocks: int, k: int, bound: str):
    """Fully fused datapath: score, stack, threshold, mask, count — on chip.

    scal0 (8, BC) rows = [d0, ||δ||², ⟨x_c,δ⟩, ||δ||, rho, valid, is_delta, ·];
    lvls (4, BC) rows = level-ℓ [proj, norm, rho, ·];
    params (Q, 8) in SMEM, row = [qn, w0..w3, bias, z·resid_std, resid_std].
    counts_out (Q·2L,) in SMEM: per query, slots [0, L) hold Σ alive after
    each level, slots [L, 2L) the delta-page survivor split the ledger
    bills to delta:cxl.
    """
    qi = pl.program_id(0)
    lv = pl.program_id(1)
    ci = pl.program_id(2)
    row = pl.ds(ci, 1)
    params = tuple(params_ref[qi, j] for j in range(8))
    align = _align_rows(packed_ref, qplanes_ref)

    @pl.when(lv == 0)
    def _level0():
        est, est_raw, margin = _score_block(align, _planes(scal0_ref, 5),
                                            params)
        lo, hi = _level0_bounds(est, est_raw, margin, params, bound)
        est_s[row, :] = est
        lo_s[row, :] = lo
        hi_s[row, :] = hi
        alive_s[row, :] = scal0_ref[5:6, :]
        delta_s[row, :] = scal0_ref[6:7, :]

    @pl.when(lv > 0)
    def _deeper():
        est, lo, hi = _deeper_bounds(est_s[row, :], align,
                                     _planes(lvls_ref, 3), params)
        est_s[row, :] = est
        lo_s[row, :] = lo
        hi_s[row, :] = hi

    @pl.when(ci == n_blocks - 1)
    def _prune_level():
        # end of a level segment: every block's bounds are in scratch, so
        # the pruning threshold (kth-smallest upper bound among survivors)
        # is computable on-chip; carry it through SMEM and update the alive
        # mask + survivor counters for the whole candidate set at once.
        amask = alive_s[...] > 0.0
        tau_s[lv] = _kth_smallest(jnp.where(amask, hi_s[...], jnp.inf), k)
        alive_new = amask & (lo_s[...] <= tau_s[lv])
        alive_s[...] = alive_new.astype(jnp.float32)
        base = qi * (2 * num_levels)
        counts_out[base + lv] = jnp.sum(alive_new.astype(jnp.int32))
        is_delta = delta_s[...] > 0.0
        counts_out[base + num_levels + lv] = jnp.sum(
            (alive_new & is_delta).astype(jnp.int32))

    @pl.when(jnp.logical_and(lv == num_levels - 1, ci == n_blocks - 1))
    def _emit():
        est_out[...] = est_s[...]
        alive_out[...] = (alive_s[...] > 0.0).astype(jnp.int32)


def _fused_bounds_kernel(packed_ref, qplanes_ref, scal0_ref, lvls_ref,
                         params_ref, est_out, lo_out, hi_out, est_s, *,
                         num_levels: int, bound: str):
    """Sharded variant: same single-launch VMEM level stacking, but emit
    each level's certified (lo, hi) instead of masking on-chip — pruning
    thresholds must be pooled ACROSS shards (a mesh collective), which
    cannot run inside a kernel.  The caller's alive chain over these
    bounds is arithmetically identical to ``_fused_kernel``'s."""
    qi = pl.program_id(0)
    lv = pl.program_id(1)
    ci = pl.program_id(2)
    row = pl.ds(ci, 1)
    params = tuple(params_ref[qi, j] for j in range(8))
    align = _align_rows(packed_ref, qplanes_ref)

    @pl.when(lv == 0)
    def _level0():
        est, est_raw, margin = _score_block(align, _planes(scal0_ref, 5),
                                            params)
        lo, hi = _level0_bounds(est, est_raw, margin, params, bound)
        est_s[row, :] = est
        lo_out[row, :] = lo
        hi_out[row, :] = hi

    @pl.when(lv > 0)
    def _deeper():
        est, lo, hi = _deeper_bounds(est_s[row, :], align,
                                     _planes(lvls_ref, 3), params)
        est_s[row, :] = est
        lo_out[row, :] = lo
        hi_out[row, :] = hi

    @pl.when(lv == num_levels - 1)
    def _emit():
        est_out[row, :] = est_s[row, :]


def _fused_in_specs(block_c: int, g: int):
    """Input block specs shared by both fused kernels (grid (Q, L, B))."""
    return [
        pl.BlockSpec((None, None, g, block_c),
                     lambda qi, lv, ci: (lv, qi, 0, ci)),
        pl.BlockSpec((None, g, 5), lambda qi, lv, ci: (qi, 0, 0)),
        pl.BlockSpec((None, 8, block_c), lambda qi, lv, ci: (qi, 0, ci)),
        pl.BlockSpec((None, None, 4, block_c),
                     lambda qi, lv, ci: (lv, qi, 0, ci)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]


def _query_rows(nb: int, block_c: int):
    """Output block holding one query's whole candidate set as (nb, BC)."""
    return pl.BlockSpec((None, nb, block_c), lambda qi, lv, ci: (qi, 0, 0))


@functools.partial(jax.jit, static_argnames=("k", "bound", "block_c",
                                             "interpret"))
def ternary_refine_fused(packed: jax.Array, q_planes: jax.Array,
                         scalars: jax.Array, level_scalars: jax.Array,
                         params: jax.Array, *, k: int, bound: str,
                         block_c: int = 512, interpret: bool | None = None
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Persistent multi-level refine: ALL TRQ levels in one launch.

    Candidate-minor layout: packed (L, Q, G, C) uint8 per-level per-query
    gathered codes; q_planes (Q, G, 5); scalars (Q, 8, C) f32 rows
    [d0, ||δ||², ⟨x_c,δ⟩, ||δ||, rho, valid, is_delta, ·];
    level_scalars (L, Q, 4, C) f32 rows [proj, norm, rho, ·] (level-0 plane
    is a placeholder — level 0 scores from ``scalars``); params (Q, 8) f32
    [qn, w0..w3, bias, z·resid_std, resid_std].

    Returns (est (Q, C) f32, alive (Q, C) int32, counts (Q, 2L) int32):
    the final calibrated estimates, the post-level-(L−1) survivor mask,
    and per-level survivor counts (total, then delta-split) — everything
    the executor's ledger and rerank need, with no intermediate HBM
    round-trips.  C must be a multiple of block_c (ops.py pads) and
    ``k ≥ 1`` is the top-k pruning width.
    """
    l, nq, g, c = packed.shape
    assert c % block_c == 0, (c, block_c)
    nb = c // block_c
    kernel = functools.partial(_fused_kernel, num_levels=l, n_blocks=nb,
                               k=k, bound=bound)
    est, alive, counts = pl.pallas_call(
        kernel,
        grid=(nq, l, nb),
        in_specs=_fused_in_specs(block_c, g),
        out_specs=[
            _query_rows(nb, block_c),
            _query_rows(nb, block_c),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, nb, block_c), jnp.float32),
            jax.ShapeDtypeStruct((nq, nb, block_c), jnp.int32),
            jax.ShapeDtypeStruct((nq * 2 * l,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nb, block_c), jnp.float32),    # running estimate
            pltpu.VMEM((nb, block_c), jnp.float32),    # certified lower bound
            pltpu.VMEM((nb, block_c), jnp.float32),    # certified upper bound
            pltpu.VMEM((nb, block_c), jnp.float32),    # alive mask (0/1)
            pltpu.VMEM((nb, block_c), jnp.float32),    # delta-page flag (0/1)
            pltpu.SMEM((l,), jnp.float32),    # per-level pruning thresholds
        ],
        interpret=resolve_interpret(interpret),
    )(packed, q_planes, scalars, level_scalars, params)
    return (est.reshape(nq, c), alive.reshape(nq, c),
            counts.reshape(nq, 2 * l))


@functools.partial(jax.jit, static_argnames=("bound", "block_c",
                                             "interpret"))
def ternary_refine_fused_bounds(packed: jax.Array, q_planes: jax.Array,
                                scalars: jax.Array,
                                level_scalars: jax.Array,
                                params: jax.Array, *, bound: str,
                                block_c: int = 512,
                                interpret: bool | None = None
                                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sharded form of ``ternary_refine_fused``: same inputs and the same
    single-launch VMEM level stacking, returning (est (Q, C),
    lo (Q, L, C), hi (Q, L, C)) so the caller can pool each level's
    pruning threshold across a ``shard_map`` axis.  Bit-identical per
    candidate to the fused kernel (the arithmetic is shared)."""
    l, nq, g, c = packed.shape
    assert c % block_c == 0, (c, block_c)
    nb = c // block_c
    kernel = functools.partial(_fused_bounds_kernel, num_levels=l,
                               bound=bound)
    level_rows = pl.BlockSpec((None, None, nb, block_c),
                              lambda qi, lv, ci: (qi, lv, 0, 0))
    est, lo, hi = pl.pallas_call(
        kernel,
        grid=(nq, l, nb),
        in_specs=_fused_in_specs(block_c, g),
        out_specs=[_query_rows(nb, block_c), level_rows, level_rows],
        out_shape=[
            jax.ShapeDtypeStruct((nq, nb, block_c), jnp.float32),
            jax.ShapeDtypeStruct((nq, l, nb, block_c), jnp.float32),
            jax.ShapeDtypeStruct((nq, l, nb, block_c), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nb, block_c), jnp.float32),    # running estimate
        ],
        interpret=resolve_interpret(interpret),
    )(packed, q_planes, scalars, level_scalars, params)
    return est.reshape(nq, c), lo.reshape(nq, l, c), hi.reshape(nq, l, c)
