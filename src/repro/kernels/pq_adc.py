"""PQ asymmetric-distance (ADC) Pallas kernel.

GPU ADC is a table-gather per subspace; TPU has no fast per-lane gather,
so we ADAPT: the lookup becomes a one-hot × LUT contraction that the MXU
executes as a matmul (hardware adaptation note in DESIGN.md §2).  For one
candidate block:

    onehot (BC, M·K) @ lut.flat (M·K,)  →  d̂₀ (BC,)

The one-hot is built in VMEM from a broadcasted iota comparison — never
touches HBM.  K=256, M≤64 keeps the block working set ≤ a few MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ternary_refine import resolve_interpret


def _adc_kernel(codes_ref, lut_ref, out_ref):
    codes = codes_ref[...].astype(jnp.int32)            # (BC, M)
    lut = lut_ref[...]                                  # (M, K)
    bc, m = codes.shape
    k = lut.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bc, m, k), 2)
    onehot = (iota == codes[:, :, None]).astype(jnp.float32)
    d = jnp.dot(onehot.reshape(bc, m * k), lut.reshape(m * k),
                preferred_element_type=jnp.float32)     # MXU matvec
    out_ref[:, 0] = d


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def pq_adc(codes: jax.Array, lut: jax.Array, *, block_c: int = 128,
           interpret: bool | None = None) -> jax.Array:
    """codes (C, M) uint8, lut (M, K) f32 → distances (C,) f32.

    C must be a multiple of block_c (ops.py pads).  VMEM: the (BC, M, K)
    one-hot at BC=128, M=16, K=256 is 2 MiB — sized for double buffering.
    """
    c, m = codes.shape
    k = lut.shape[1]
    assert c % block_c == 0, (c, block_c)
    out = pl.pallas_call(
        _adc_kernel,
        grid=(c // block_c,),
        in_specs=[
            pl.BlockSpec((block_c, m), lambda i: (i, 0)),
            pl.BlockSpec((m, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_c, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, 1), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(codes, lut)
    return out[:, 0]
