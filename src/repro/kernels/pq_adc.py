"""PQ asymmetric-distance (ADC) Pallas kernel: the front's scorer on a TPU.

A PQ code scores as d̂₀ = Σ_m lut[m, code[m]].  A GPU does that as a table
gather per subspace; a TPU has no fast per-lane gather (XLA's gather ran at
20–37 cycles per looked-up entry on a TPU v5e), so the lookup becomes a
one-hot × LUT contraction on the MXU, built in VMEM and never written to
HBM.

The code byte is split into digits (a, b) = divmod(code, KB), KB = 32.  For
a chunk of MC = 128 / KB = 4 subspaces, the one-hot of the low digits,
(MC·KB = 128, BC) — one MXU depth — is contracted with the chunk's LUT laid
out block-diagonally as (MC·KA, MC·KB), KA = ⌈K / KB⌉ (8 at K = 256).  That
gives, per candidate and subspace, the KA entries lut[m, a·KB + b]; a
compare with the high digit a keeps one.  So the MXU streams 3·MC·KA rows
per chunk instead of taking MC·K one-hot rows as weights, and the VPU
compares MC·(KA + KB) values instead of MC·K.  (On a TPU v5e, 32 queries ×
46,880 candidates × 96 subspaces took 7.4 ms at KB = 32, 13.6 at KB = 16
and 9.4 at KB = 64.)

Precision stays float32.  Each LUT entry is split into three bfloat16 parts
(hi, mid, lo: the top 8 significant bits, the next 8, the rest), cut by bit
masks so that no step rounds; a 0/1 one-hot times a part is exact, each
output entry of the contraction has one nonzero term, and (hi + mid) + lo
restores the f32 entry bit for bit.  Only the order of the sum over
subspaces differs from the gather's.

Layout: candidates lie along lanes, as in the fused refine kernels.  The
gathered codes are laid out per call as (Q, M, C), so a subspace's codes
are a (1, BC) row that broadcasts along sublanes.  The grid walks
(query, candidate block); a query's LUT parts stay resident in VMEM across
its blocks.  The candidate block and the VMEM limit follow from (C, M, K)
and the VMEM budget (``adc_plan``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ternary_refine import VMEM_BUDGET_BYTES, resolve_interpret

_DEPTH = 128       # MXU contraction depth: one chunk's one-hot rows
_KB = 32           # low-digit base
_MC = _DEPTH // _KB    # subspaces per chunk
_GROUP = 8         # subspaces per loop step: one int32 sublane tile
_PARTS = 3         # bf16 parts of an f32 LUT entry
_BLOCKS = (2048, 1024, 512, 256, 128)


def use_kernel() -> bool:
    """Whether ``anns.stages.adc_score`` scores with this kernel: on a TPU,
    where XLA's table gather is slow; the gather everywhere else.  Decided
    when the front is traced, like ``resolve_interpret``."""
    return jax.default_backend() == "tpu"


class ADCPlan(NamedTuple):
    """Static shape of one ADC launch."""

    ka: int            # high-digit range ⌈K / KB⌉, even: whole 8-row tiles
    m_pad: int         # subspaces padded to whole loop steps
    block_c: int       # candidates per grid step (lanes)
    vmem_limit: int    # scoped-VMEM limit handed to the compiler

    @property
    def rows(self) -> int:
        """LUT-part rows of a chunk: (part, subspace, high digit)."""
        return _PARTS * _MC * self.ka


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def adc_plan(c: int, m: int, k: int) -> ADCPlan:
    """Candidate block and VMEM limit for C candidates of M subspaces of K
    centroids: the widest block whose working set fits half the VMEM
    budget (the rest is the compiler's), and no wider than the candidates
    padded to a lane tile."""
    ka = _round_up(-(-k // _KB), _GROUP // _MC)
    m_pad = _round_up(m, _GROUP)
    rows = _PARTS * _MC * ka
    lut = 2 * (m_pad // _MC) * rows * _DEPTH * 2     # resident parts, x2 bufs
    per_lane = (2 * m_pad                            # uint8 codes, x2 bufs
                + 4 * m_pad                          # int32 codes scratch
                + 6 * _DEPTH                         # one-hot f32 → bf16
                + 4 * rows                           # contraction output
                + 3 * 4 * _MC * ka                   # entries, mask, select
                + 4 * _GROUP + 2 * 4)                # accumulator, output
    c_lanes = _round_up(c, 128)
    block_c = next((b for b in _BLOCKS if b <= c_lanes
                    and lut + b * per_lane <= VMEM_BUDGET_BYTES // 2),
                   _BLOCKS[-1])
    need = lut + block_c * per_lane
    return ADCPlan(ka=ka, m_pad=m_pad, block_c=block_c,
                   vmem_limit=max(VMEM_BUDGET_BYTES, 2 * need))


def _bf16_parts(t: jax.Array) -> tuple[jax.Array, ...]:
    """f32 → (hi, mid, lo) bf16 with (hi + mid) + lo == t exactly.  Each
    part is cut from the bits (the top 8 significant bits, then the next 8
    of the remainder), so no conversion rounds and a compiler that drops
    an f32 → bf16 → f32 round trip cannot change the result."""
    def top(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
    hi = top(t)
    rest = t - hi
    mid = top(rest)
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, rest - mid))


def _lut_parts(tables: jax.Array, plan: ADCPlan) -> jax.Array:
    """(Q, M, K) f32 LUTs → (Q, M_pad / MC, rows, 128) bf16 chunk blocks.

    Row (x, i, a) of chunk j, column (i', b): part x of
    lut[j·MC + i, a·KB + b] where i = i', else 0.  Padded subspaces and
    centroids are 0, so they add exactly nothing."""
    nq, m, k = tables.shape
    ka = plan.ka
    t = jnp.pad(tables.astype(jnp.float32),
                ((0, 0), (0, plan.m_pad - m), (0, ka * _KB - k)))
    parts = jnp.stack(_bf16_parts(t), axis=2)           # (Q, Mp, 3, KA·KB)
    parts = parts.reshape(nq, plan.m_pad // _MC, _MC, _PARTS, ka, 1, _KB)
    parts = parts.transpose(0, 1, 3, 2, 4, 5, 6)        # (Q, J, x, i, a, 1, b)
    eye = jnp.eye(_MC, dtype=jnp.bfloat16)[:, None, :, None]  # (i, 1, i', 1)
    blocks = parts * eye                                # (Q, J, x, i, a, i', b)
    return blocks.reshape(nq, plan.m_pad // _MC, plan.rows, _DEPTH)


def _adc_kernel(codes_ref, parts_ref, out_ref, codes_s, *, plan: ADCPlan):
    """One query's candidate block: codes (M_pad, BC) uint8, LUT chunk
    blocks (M_pad / MC, rows, 128) bf16 → d̂₀ (1, BC) f32."""
    ka = plan.ka
    bc = out_ref.shape[-1]
    r = _MC * ka
    codes_s[...] = codes_ref[...].astype(jnp.int32)
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (_KB, bc), 0)
    a_iota = jnp.concatenate(
        [jax.lax.broadcasted_iota(jnp.int32, (ka, bc), 0)] * _MC, axis=0)

    def step(g, acc):
        codes = codes_s[pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP), :]
        low = codes & (_KB - 1)
        high = codes >> (_KB.bit_length() - 1)
        for t in range(_GROUP // _MC):
            subs = range(t * _MC, (t + 1) * _MC)
            onehot = jnp.concatenate(
                [jnp.where(low[i:i + 1] == b_iota, 1.0, 0.0
                           ).astype(jnp.bfloat16) for i in subs], axis=0)
            y = jnp.dot(parts_ref[g * (_GROUP // _MC) + t], onehot,
                        preferred_element_type=jnp.float32)   # (3R, BC)
            entry = (y[:r] + y[r:2 * r]) + y[2 * r:]          # (R, BC)
            pick = jnp.concatenate(
                [jnp.broadcast_to(high[i:i + 1], (ka, bc)) for i in subs],
                axis=0) == a_iota
            entry = jnp.where(pick, entry, 0.0)
            for s in range(0, r, _GROUP):
                acc = acc + entry[s:s + _GROUP]
        return acc

    acc = jax.lax.fori_loop(0, plan.m_pad // _GROUP, step,
                            jnp.zeros((_GROUP, bc), jnp.float32))
    out_ref[...] = jnp.sum(acc, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pq_adc_batch(codes: jax.Array, tables: jax.Array, *,
                 interpret: bool | None = None) -> jax.Array:
    """codes (Q, C, M) uint8 gathered per query, tables (Q, M, K) f32 →
    d̂₀ (Q, C) f32, d̂₀[q, c] = Σ_m tables[q, m, codes[q, c, m]].

    Any C: the last candidate block is padded inside the op."""
    nq, c, m = codes.shape
    k = tables.shape[2]
    plan = adc_plan(c, m, k)
    bc = plan.block_c
    c_pad = _round_up(c, bc)
    codes_t = jnp.pad(jnp.swapaxes(codes, 1, 2),
                      ((0, 0), (0, plan.m_pad - m), (0, c_pad - c)))
    parts = _lut_parts(tables, plan)
    n_chunks = parts.shape[1]
    out = pl.pallas_call(
        functools.partial(_adc_kernel, plan=plan),
        grid=(nq, c_pad // bc),
        in_specs=[
            pl.BlockSpec((None, plan.m_pad, bc), lambda q, j: (q, 0, j)),
            pl.BlockSpec((None, n_chunks, plan.rows, _DEPTH),
                         lambda q, j: (q, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, bc), lambda q, j: (q, 0, j)),
        out_shape=jax.ShapeDtypeStruct((nq, 1, c_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((plan.m_pad, bc), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=plan.vmem_limit),
        interpret=resolve_interpret(interpret),
    )(codes_t, parts)
    return out[:, 0, :c]
