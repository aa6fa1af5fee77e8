"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps + hypothesis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from compat import given, settings, st

from repro.core.packing import pack_ternary, packed_size
from repro.core.ternary import ternary_encode
from repro.core import trq as trq_mod
from repro.anns import stages
from repro.kernels import ops as kernel_ops
from repro.kernels import ref
from repro.kernels.ops import (VMEMBudgetError, adc_scores,
                               fused_refine_bounds_batch,
                               fused_refine_scores_batch, refine_scores)


def _setup_refine(c, d, seed=0):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (c, d))
    x_c = x + 0.2 * jax.random.normal(ks[1], (c, d))
    delta = x - x_c
    tc = ternary_encode(delta)
    packed = pack_ternary(tc.code)
    q = jax.random.normal(ks[2], (d,))
    d0 = jnp.sum((q[None] - x_c) ** 2, axis=-1)
    delta_sq = jnp.sum(delta * delta, axis=-1)
    cross = jnp.sum(x_c * delta, axis=-1)
    w = jnp.asarray([1.0, 1.1, 0.95, 2.1])
    bias = jnp.asarray(0.3)
    return packed, q, d0, delta_sq, cross, tc.norm, tc.rho, w, bias


class TestTernaryRefineKernel:
    @pytest.mark.parametrize("c,d", [(64, 65), (128, 128), (300, 768),
                                     (1000, 1536), (7, 5), (512, 100)])
    def test_matches_ref(self, c, d):
        args = _setup_refine(c, d, seed=c + d)
        out = refine_scores(*args)
        expect = ref.ternary_refine_ref(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_core_estimator(self):
        # The kernel must agree with the system's reference refine path.
        from repro.core.calibration import CalibrationModel
        from repro.core.decomposition import RecordScalars
        from repro.core.estimator import refine_level
        c, d = 200, 256
        packed, q, d0, delta_sq, cross, norm, rho, w, bias = _setup_refine(
            c, d, seed=3)
        out = refine_scores(packed, q, d0, delta_sq, cross, norm, rho, w,
                            bias)
        model = CalibrationModel(w=w, bias=bias,
                                 resid_std=jnp.asarray(0.0))
        scalars = RecordScalars(delta_sq=delta_sq, cross=cross, rho=rho,
                                norm=norm)
        from repro.core.packing import unpack_ternary
        codes = unpack_ternary(packed, d)
        state = refine_level(q, d0, scalars, codes, model, k=10)
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(state.est), rtol=2e-5,
                                   atol=2e-5)
        # certified interval identical: lo = est_raw - margin
        np.testing.assert_allclose(np.asarray(out[:, 1] - out[:, 2]),
                                   np.asarray(state.lo), rtol=2e-5,
                                   atol=2e-5)

    @given(st.integers(1, 400), st.integers(2, 900), st.integers(0, 99))
    @settings(max_examples=12, deadline=None)
    def test_property_shapes(self, c, d, seed):
        args = _setup_refine(c, d, seed=seed)
        out = refine_scores(*args)
        expect = ref.ternary_refine_ref(*args)
        assert out.shape == (c, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=3e-5, atol=3e-5)

    # dims not divisible by 5 (packing pad) × candidate counts not divisible
    # by block_c (ops.py row pad): the kernel must agree with the reference
    # estimator path on est, est_raw (→ lo), and margin.
    @pytest.mark.parametrize("c,d,block_c", [(130, 63, 64), (300, 77, 128),
                                             (65, 129, 64), (513, 251, 256)])
    def test_parity_with_estimator_odd_shapes(self, c, d, block_c):
        from repro.core.calibration import CalibrationModel
        from repro.core.decomposition import RecordScalars
        from repro.core.estimator import refine_level
        from repro.core.packing import unpack_ternary

        packed, q, d0, delta_sq, cross, norm, rho, w, bias = _setup_refine(
            c, d, seed=c * d)
        out = refine_scores(packed, q, d0, delta_sq, cross, norm, rho, w,
                            bias, block_c=block_c)
        model = CalibrationModel(w=w, bias=bias, resid_std=jnp.asarray(0.0))
        scalars = RecordScalars(delta_sq=delta_sq, cross=cross, rho=rho,
                                norm=norm)
        state = refine_level(q, d0, scalars, unpack_ternary(packed, d),
                             model, k=10)
        assert out.shape == (c, 3)
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(state.est), rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(out[:, 1] - out[:, 2]),
                                   np.asarray(state.lo), rtol=2e-5,
                                   atol=2e-5)


class TestBatchedRefineKernel:
    @pytest.mark.parametrize("nq,c,d,block_c", [(3, 130, 63, 64),
                                                (5, 512, 100, 256),
                                                (1, 7, 11, 64)])
    def test_matches_per_query_kernel(self, nq, c, d, block_c):
        from repro.kernels.ops import refine_scores_batch

        per_query = []
        packed_b, d0_b, dsq_b, cross_b, norm_b, rho_b, q_b = \
            [], [], [], [], [], [], []
        for i in range(nq):
            packed, q, d0, delta_sq, cross, norm, rho, w, bias = \
                _setup_refine(c, d, seed=100 + i)
            per_query.append(refine_scores(packed, q, d0, delta_sq, cross,
                                           norm, rho, w, bias,
                                           block_c=block_c))
            packed_b.append(packed); q_b.append(q); d0_b.append(d0)
            dsq_b.append(delta_sq); cross_b.append(cross)
            norm_b.append(norm); rho_b.append(rho)
        out = refine_scores_batch(jnp.stack(packed_b), jnp.stack(q_b),
                                  jnp.stack(d0_b), jnp.stack(dsq_b),
                                  jnp.stack(cross_b), jnp.stack(norm_b),
                                  jnp.stack(rho_b), w, bias,
                                  block_c=block_c)
        assert out.shape == (nq, c, 3)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.stack(per_query)),
                                   rtol=2e-5, atol=2e-5)


def _setup_trq(seed, levels, n=400, d=24, nq=3, n_cents=8):
    """Calibrated multi-level TRQ problem with the whole database as the
    candidate set (so exact top-k is contained in it)."""
    key = jax.random.PRNGKey(seed)
    kx, kq, kc, kcal, kp = jax.random.split(key, 5)
    x = jax.random.normal(kx, (n, d))
    cents = jax.random.normal(kc, (n_cents, d))
    assign = jnp.argmin(jnp.sum((x[:, None] - cents[None]) ** 2, -1), -1)
    x_c = cents[assign]
    codes, _ = trq_mod.encode_database(x, x_c, num_levels=levels)
    qcal = jax.random.normal(kcal, (64, d))
    pair = jax.random.randint(kp, (64,), 0, n)
    codes = trq_mod.calibrate(codes, qcal, x, x_c, pair)
    qs = jax.random.normal(kq, (nq, d))
    ids = jnp.broadcast_to(jnp.arange(n)[None], (nq, n))
    valid = jnp.ones((nq, n), bool)
    d0 = jnp.sum((x_c[ids] - qs[:, None]) ** 2, -1)
    d_true = jnp.sum((x[ids] - qs[:, None]) ** 2, -1)
    return codes, qs, ids, valid, d0, d_true


def _fused_args(codes, qs, ids, valid, d0, is_delta=None):
    """Assemble the raw fused-wrapper argument tuple from a TRQ problem."""
    sc = codes.scalars
    if is_delta is None:
        is_delta = jnp.zeros_like(valid)
    return (jnp.stack([lv.packed[ids] for lv in codes.levels]), qs, d0,
            sc.delta_sq[ids], sc.cross[ids], sc.norm[ids], sc.rho[ids],
            valid, is_delta,
            jnp.stack([lv.proj[ids] for lv in codes.levels]),
            jnp.stack([lv.norm[ids] for lv in codes.levels]),
            jnp.stack([lv.rho[ids] for lv in codes.levels]),
            codes.model.w, codes.model.bias, codes.model.resid_std, 3.0)


def _count_pallas_calls(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                n += _count_pallas_calls(inner)
    return n


class TestFusedRefineKernel:
    """The persistent multi-level kernel vs the reference refine chain."""

    @pytest.mark.parametrize("bound", ["cauchy", "quantile"])
    @pytest.mark.parametrize("levels", [1, 2, 3])
    def test_matches_reference_backend(self, bound, levels):
        codes, qs, ids, valid, d0, _ = _setup_trq(levels * 17, levels)
        est_r, level_alive = stages._reference_refine(
            qs, d0, ids, valid, codes, k=5, bound=bound, z=3.0)
        est_p, alive_p, counters = stages._pallas_refine(
            qs, d0, ids, valid, None, codes, k=5, bound=bound, z=3.0,
            block_c=64, axis_name=None)
        np.testing.assert_allclose(np.asarray(est_p), np.asarray(est_r),
                                   rtol=3e-5, atol=3e-5)
        assert jnp.array_equal(alive_p, level_alive[-1])
        ref_counters = stages._level_counters(level_alive)
        assert {k2: int(v) for k2, v in counters.items()} == \
            {k2: int(v) for k2, v in ref_counters.items()}

    @pytest.mark.parametrize("bound", ["cauchy", "quantile"])
    def test_bounds_variant_bitwise_matches_onchip(self, bound):
        """The sharded (bounds-emitting) form + the jnp alive chain must be
        BIT-identical to the on-chip pruning form — that is what makes
        sharded and unsharded pallas runs bit-identical."""
        levels, k = 3, 5
        codes, qs, ids, valid, d0, _ = _setup_trq(29, levels)
        est_a, alive_a, _ = stages._pallas_refine(
            qs, d0, ids, valid, None, codes, k=k, bound=bound, z=3.0,
            block_c=64, axis_name=None)
        args = _fused_args(codes, qs, ids, valid, d0)
        est_b, lo, hi = fused_refine_bounds_batch(*args, bound=bound,
                                                  block_c=64)
        alive = valid
        for lv in range(levels):
            tau = stages._topk_threshold_batch(hi[:, lv], alive, k, None)
            alive = alive & (lo[:, lv] <= tau[:, None])
        assert jnp.array_equal(est_a, est_b)
        assert jnp.array_equal(alive_a, alive)

    def test_block_c_invariant(self):
        """Candidate blocking must not change the survivor set or the
        ledger counters (estimates may differ in ulps: XLA picks its f32
        reduction strategy per block shape)."""
        codes, qs, ids, valid, d0, _ = _setup_trq(31, 2)
        outs = [stages._pallas_refine(qs, d0, ids, valid, None, codes, k=5,
                                      bound="cauchy", z=3.0, block_c=bc,
                                      axis_name=None)
                for bc in (64, 128, 512)]
        for est, alive, counters in outs[1:]:
            np.testing.assert_allclose(np.asarray(est),
                                       np.asarray(outs[0][0]),
                                       rtol=1e-6, atol=1e-6)
            assert jnp.array_equal(alive, outs[0][1])
            assert {k2: int(v) for k2, v in counters.items()} == \
                {k2: int(v) for k2, v in outs[0][2].items()}

    def test_delta_survivor_counts(self):
        """The kernel's delta-split counters must equal the mask-chain
        arithmetic the reference backend uses."""
        codes, qs, ids, valid, d0, _ = _setup_trq(37, 3)
        is_delta = jax.random.bernoulli(jax.random.PRNGKey(5), 0.3,
                                        valid.shape)
        _, level_alive = stages._reference_refine(
            qs, d0, ids, valid, codes, k=5, bound="cauchy", z=3.0)
        expect = stages._level_counters(level_alive, is_delta)
        _, _, counters = stages._pallas_refine(
            qs, d0, ids, valid, is_delta, codes, k=5, bound="cauchy",
            z=3.0, block_c=64, axis_name=None)
        assert {k2: int(v) for k2, v in counters.items()} == \
            {k2: int(v) for k2, v in expect.items()}

    @pytest.mark.parametrize("axis_name", [None, "search"])
    def test_single_kernel_launch(self, axis_name):
        """All TRQ levels run as ONE pallas_call per micro-batch — no
        per-level launches, in both the unsharded and sharded forms."""
        codes, qs, ids, valid, d0, _ = _setup_trq(41, 3)
        if axis_name is None:
            fn = lambda *a: stages._pallas_refine(
                *a, None, codes, k=5, bound="cauchy", z=3.0, block_c=64,
                axis_name=None)
            jaxpr = jax.make_jaxpr(fn)(qs, d0, ids, valid)
        else:
            args = _fused_args(codes, qs, ids, valid, d0)
            jaxpr = jax.make_jaxpr(
                lambda *a: fused_refine_bounds_batch(
                    *a, bound="cauchy", block_c=64))(*args)
        assert _count_pallas_calls(jaxpr.jaxpr) == 1

    def test_vmem_budget_named_error(self):
        codes, qs, ids, valid, d0, _ = _setup_trq(43, 2)
        args = _fused_args(codes, qs, ids, valid, d0)
        with pytest.raises(VMEMBudgetError, match="VMEM"):
            fused_refine_scores_batch(*args, k=5, bound="cauchy",
                                      block_c=1 << 22)
        with pytest.raises(VMEMBudgetError, match="VMEM"):
            fused_refine_bounds_batch(*args, bound="cauchy",
                                      block_c=1 << 22)

    def test_interpret_auto_detection(self):
        """Direct kernel calls (no interpret kwarg) must auto-detect the
        backend when traced instead of silently interpreting on TPU."""
        from repro.kernels import ternary_refine as tr
        assert tr.resolve_interpret(None) == (jax.default_backend() != "tpu")
        assert tr.resolve_interpret(True) is True
        assert tr.resolve_interpret(False) is False
        args = _setup_refine(64, 20, seed=9)
        packed, q, d0, delta_sq, cross, norm, rho, w, bias = args
        q_planes = ref.make_query_planes(q, packed.shape[1])
        scalars = jnp.stack([d0, delta_sq, cross, norm, rho] +
                            [jnp.zeros_like(d0)] * 3, axis=-1)
        params = jnp.concatenate(
            [jnp.linalg.norm(q)[None], w, bias[None],
             jnp.zeros((2,))])[None, :]
        out = tr.ternary_refine(packed, q_planes, scalars, params,
                                block_c=64)
        expect = ref.ternary_refine_ref(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)


class TestCertificationSoundness:
    """Early-exit certification property: across bounds, level depths and
    seeds, NO true top-k member (exact L2 over the candidate set) is ever
    pruned by any level's alive mask — fused kernel and reference chain."""

    @given(st.sampled_from(["cauchy", "quantile"]), st.integers(1, 3),
           st.integers(0, 99))
    @settings(max_examples=12, deadline=None)
    def test_no_true_topk_pruned(self, bound, levels, seed):
        k = 5
        codes, qs, ids, valid, d0, d_true = _setup_trq(seed, levels)
        _, top = jax.lax.top_k(-d_true, k)
        _, level_alive = stages._reference_refine(
            qs, d0, ids, valid, codes, k=k, bound=bound, z=3.0)
        for m in level_alive:                      # every level's mask
            assert bool(jnp.all(jnp.take_along_axis(m, top, axis=1)))
        _, alive_p, _ = stages._pallas_refine(
            qs, d0, ids, valid, None, codes, k=k, bound=bound, z=3.0,
            block_c=64, axis_name=None)
        assert bool(jnp.all(jnp.take_along_axis(alive_p, top, axis=1)))
        # the fused kernel's intermediate masks are the bounds variant's
        # alive chain (bit-identical, see TestFusedRefineKernel) — check
        # them level by level as well
        args = _fused_args(codes, qs, ids, valid, d0)
        _, lo, hi = fused_refine_bounds_batch(*args, bound=bound,
                                              block_c=64)
        alive = valid
        for lv in range(levels):
            tau = stages._topk_threshold_batch(hi[:, lv], alive, k, None)
            alive = alive & (lo[:, lv] <= tau[:, None])
            assert bool(jnp.all(jnp.take_along_axis(alive, top, axis=1)))


class TestADCKernel:
    # (Q, C, M, K): the batched kernel against the gather of quant.pq, per
    # query.  M ∈ {4, 96, 192} at K = 256, K ∈ {16, 32, 64}, C off the
    # candidate block (13, 130, 300, 500), Q ∈ {1, 3}.
    @pytest.mark.parametrize("nq,c,m,k", [
        (1, 64, 8, 32), (1, 128, 16, 256), (1, 500, 32, 64), (1, 13, 4, 16),
        (1, 256, 96, 256), (3, 300, 4, 256), (3, 300, 96, 256),
        (1, 200, 192, 256), (3, 130, 8, 16), (3, 129, 16, 64)])
    def test_matches_ref(self, nq, c, m, k):
        from repro.kernels.pq_adc import pq_adc_batch
        from repro.quant import pq
        key = jax.random.PRNGKey(c + m + k)
        codes = jax.random.randint(key, (nq, c, m), 0, k).astype(jnp.uint8)
        luts = jax.random.uniform(jax.random.fold_in(key, 1), (nq, m, k))
        out = pq_adc_batch(codes, luts)
        assert out.shape == (nq, c) and out.dtype == jnp.float32
        for qi in range(nq):
            np.testing.assert_allclose(
                np.asarray(out[qi]),
                np.asarray(pq.adc_distances(luts[qi], codes[qi])),
                rtol=1e-5, atol=1e-6)
        if nq == 1:        # the single-query entry is the same kernel
            np.testing.assert_array_equal(
                np.asarray(adc_scores(codes[0], luts[0])), np.asarray(out[0]))

    def test_lut_parts_restore_f32_exactly(self):
        """(hi + mid) + lo of the bf16 split is the f32 entry, bit for bit,
        each part is cut without rounding, and each chunk block is
        block-diagonal over its subspaces."""
        from repro.kernels import pq_adc as P
        luts = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 256)) * 1e3
        plan = P.adc_plan(100, 16, 256)
        parts = P._lut_parts(luts, plan).astype(jnp.float32)
        mc, ka, kb = P._MC, plan.ka, P._KB
        blocks = parts.reshape(2, 16 // mc, 3, mc, ka, mc, kb)
        diag = jnp.einsum("qjxiaib->qjxiab", blocks)
        whole = (diag[:, :, 0] + diag[:, :, 1]) + diag[:, :, 2]
        np.testing.assert_array_equal(
            np.asarray(whole.reshape(2, 16, ka * kb)), np.asarray(luts))
        # truncation, not rounding: hi and mid keep the sign of the entry
        # and no larger magnitude, so no part was rounded up
        for x in (diag[:, :, 0], diag[:, :, 1]):
            assert bool(jnp.all(jnp.abs(x) <= jnp.abs(whole)))
        eye = jnp.eye(mc, dtype=bool)[:, None, :, None]     # (i, ·, i', ·)
        assert not bool(jnp.any(jnp.where(eye, 0.0, blocks) != 0.0))

    def test_matches_pq_module(self):
        from repro.quant import pq
        from repro.data import make_embeddings
        x = make_embeddings(jax.random.PRNGKey(0), 1000, 64, clusters=8)
        cb = pq.train(jax.random.PRNGKey(1), x, m=8, k=64, iters=5)
        codes = pq.encode(cb, x[:300])
        q = x[500]
        lut = pq.adc_table(cb, q)
        out = adc_scores(codes, lut)
        expect = pq.adc_distances(lut, codes)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-4, atol=1e-5)

    @given(st.integers(1, 300), st.sampled_from([2, 4, 8, 16]),
           st.sampled_from([16, 64, 256]), st.integers(0, 99))
    @settings(max_examples=10, deadline=None)
    def test_property(self, c, m, k, seed):
        key = jax.random.PRNGKey(seed)
        codes = jax.random.randint(key, (c, m), 0, k).astype(jnp.uint8)
        lut = jax.random.normal(jax.random.fold_in(key, 1), (m, k))
        np.testing.assert_allclose(np.asarray(adc_scores(codes, lut)),
                                   np.asarray(ref.pq_adc_ref(codes, lut)),
                                   rtol=2e-5, atol=2e-5)
