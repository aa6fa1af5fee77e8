"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The fused refine kernels and the jitted Pallas refine step are compiled at
the widths ``chip_smoke.py`` runs (d=768 → G=154 code bytes, L=2 levels, a
32-query micro-batch over 47,104 padded candidates, 512-candidate blocks);
the served IVF front with its MXU ADC kernel at both benchmark shapes.
The TPU compiler refuses block shapes, scratch layouts and VMEM use that
interpret mode accepts, so these tests guard the chip path without a chip.
Nothing runs: they check that a Mosaic kernel is in the program and that
the program fits one chip's HBM.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

G, L, Q, C, BLOCK_C = 154, 2, 32, 47_104, 512
N, D = 1_000_000, 768
HBM_BYTES = 16 * 1000**3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep the cache out of these compiles
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("kernel", ["fused", "fused_bounds"])
def test_fused_kernel_compiles_for_v5e(one_chip, kernel):
    from repro.kernels import ternary_refine as tr
    args = (_spec(one_chip, (L, Q, G, C), jnp.uint8),
            _spec(one_chip, (Q, G, 5)),
            _spec(one_chip, (Q, 8, C)),
            _spec(one_chip, (L, Q, 4, C)),
            _spec(one_chip, (Q, 8)))
    if kernel == "fused":
        fn = lambda *a: tr.ternary_refine_fused(         # noqa: E731
            *a, k=10, bound="cauchy", block_c=BLOCK_C, interpret=False)
    else:
        fn = lambda *a: tr.ternary_refine_fused_bounds(  # noqa: E731
            *a, bound="cauchy", block_c=BLOCK_C, interpret=False)
    _check(jax.jit(fn).lower(*args).compile())


def test_pallas_refine_step_compiles_for_v5e(one_chip, monkeypatch):
    """The served refine step (gathers + fused kernel + counters) over a
    1M-row TRQ table, as ``PallasRefineBackend`` runs it on a TPU."""
    from repro.anns import stages
    from repro.core import calibration as calib
    from repro.core import trq as trq_mod
    from repro.core.decomposition import RecordScalars
    from repro.kernels import ternary_refine as tr

    # the kernels pick compiled vs interpreted from the process's default
    # backend, which is the CPU here: steer them to the compiled kernel
    monkeypatch.setattr(tr, "resolve_interpret",
                        lambda interpret: False if interpret is None
                        else bool(interpret))
    jax.clear_caches()
    s = lambda shape, dtype=jnp.float32: _spec(one_chip, shape, dtype)  # noqa: E731
    level = trq_mod.TRQLevel(packed=s((N, G), jnp.uint8), proj=s((N,)),
                             norm=s((N,)), rho=s((N,)))
    codes = trq_mod.TRQCodes(
        dim=D, levels=(level,) * L,
        scalars=RecordScalars(delta_sq=s((N,)), cross=s((N,)),
                              rho=s((N,)), norm=s((N,))),
        model=calib.CalibrationModel(w=s((4,)), bias=s(()),
                                     resid_std=s(())))
    step = jax.jit(lambda q, d0, ids, valid, trq: stages._pallas_refine(
        q, d0, ids, valid, None, trq, k=10, bound="cauchy", z=3.0,
        block_c=BLOCK_C))
    compiled = step.lower(s((Q, D)), s((Q, C)), s((Q, C), jnp.int32),
                          s((Q, C), jnp.bool_), codes).compile()
    jax.clear_caches()
    _check(compiled)


@pytest.mark.parametrize("n,d,m,cap", [(1_000_000, 768, 96, 2_930),
                                       (500_000, 1_536, 192, 1_713)])
def test_ivf_front_compiles_for_v5e(one_chip, monkeypatch, n, d, m, cap):
    """The served front (probe, code gather, ADC) at the benchmark's
    shapes: 16 probed lists of ``cap`` slots, 46,880 and 27,408 candidates
    a query.  It scores on the MXU kernel, and no (Q, C, M) f32 table
    lookup (576 and 674 MB) is left among its temporaries."""
    from repro.anns import stages
    from repro.index import ivf as ivf_mod
    from repro.kernels import pq_adc
    from repro.quant import pq as pq_mod

    # the CPU is the default backend here: steer the front onto the kernel,
    # and the kernel to its compiled form
    monkeypatch.setattr(pq_adc, "use_kernel", lambda: True)
    monkeypatch.setattr(pq_adc, "resolve_interpret",
                        lambda interpret: False if interpret is None
                        else bool(interpret))
    jax.clear_caches()
    s = lambda shape, dtype=jnp.float32: _spec(one_chip, shape, dtype)  # noqa: E731
    nlist, nprobe, k = 1024, 16, 256
    ivf = ivf_mod.IVFIndex(centroids=s((nlist, d)),
                           lists=s((nlist, cap), jnp.int32),
                           list_len=s((nlist,), jnp.int32))
    codebook = pq_mod.PQCodebook(codebooks=s((m, k, d // m)))
    front = jax.jit(lambda ivf, cb, codes, q, qv: stages._ivf_candidates(
        ivf, cb, codes, q, qv, nprobe=nprobe))
    compiled = front.lower(ivf, codebook, s((n, m), jnp.uint8), s((Q, d)),
                           s((Q,), jnp.bool_)).compile()
    jax.clear_caches()
    _check(compiled)
    lookup = Q * nprobe * cap * m * 4
    assert compiled.memory_analysis().temp_size_in_bytes < lookup
