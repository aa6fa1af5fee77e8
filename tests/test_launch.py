"""Launch-layer unit tests: sharding specs, roofline parsing, input specs.

(The real multi-pod compile check is launch/dryrun.py — these tests cover
the pure-Python logic so failures localize.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from compat import given, settings, st
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, shape_applicable
from repro.launch import roofline
from repro.launch.input_specs import (cache_structs, params_structs,
                                      prefill_batch_specs,
                                      train_batch_specs)
from repro.models.model_zoo import build_model


class FakeMesh:
    axis_names = ("data", "model")

    class devices:
        shape = (16, 16)
        size = 256


def _specs(params):
    from repro.launch import shardings as sh
    return sh.param_specs(FakeMesh, params)


class TestParamSpecs:
    @pytest.mark.parametrize("name", sorted(ARCHS))
    def test_divisibility_everywhere(self, name):
        """Every sharded dim must divide by its mesh axes — for all archs."""
        api = build_model(ARCHS[name])
        params = params_structs(api)
        specs = _specs(params)
        sizes = {"data": 16, "model": 16, ("data",): 16}

        flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for (path, leaf), spec in zip(flat_p, flat_s):
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                axs = ax if isinstance(ax, tuple) else (ax,)
                div = 1
                for a in axs:
                    div *= 16
                assert leaf.shape[dim] % div == 0, \
                    (jax.tree_util.keystr(path), leaf.shape, spec)

    def test_large_weights_are_sharded(self):
        api = build_model(ARCHS["qwen2-72b"])
        params = params_structs(api)
        specs = _specs(params)
        flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for (path, leaf), spec in zip(flat_p, flat_s):
            n = int(np.prod(leaf.shape))
            if n >= 2**24:   # ≥16M params must not be replicated
                assert any(ax is not None for ax in spec), \
                    (jax.tree_util.keystr(path), leaf.shape)


class TestRooflineParsing:
    def test_shape_bytes(self):
        assert roofline.shape_bytes("bf16[256,1024]{1,0}") == 256 * 1024 * 2
        assert roofline.shape_bytes("(f32[8], s32[4])") == 32 + 16
        assert roofline.shape_bytes("token[]") == 0

    def test_collective_parse(self):
        hlo = """
  %ag = bf16[512,1024]{1,0} all-gather(bf16[32,1024]{1,0} %x), dims={0}
  %ar.1 = f32[4096]{0} all-reduce(f32[4096]{0} %y), to_apply=%add
  %rs = f32[256]{0} reduce-scatter(f32[4096]{0} %z), dimensions={0}
  %cp = bf16[64,64]{1,0} collective-permute(bf16[64,64]{1,0} %w)
  %nothing = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)
"""
        stats = roofline.collective_bytes(hlo)
        assert stats.count_by_op == {"all-gather": 1, "all-reduce": 1,
                                     "reduce-scatter": 1,
                                     "collective-permute": 1}
        assert stats.bytes_by_op["all-gather"] == 512 * 1024 * 2
        assert stats.bytes_by_op["all-reduce"] == 4096 * 4 * 2  # ring 2x

    def test_async_start_done_counted_once(self):
        hlo = """
  %ags = bf16[512]{0} all-gather-start(bf16[32]{0} %x), dims={0}
  %agd = bf16[512]{0} all-gather-done(bf16[512]{0} %ags)
"""
        stats = roofline.collective_bytes(hlo)
        assert stats.count_by_op.get("all-gather", 0) == 1

    @given(st.integers(1, 10_000), st.sampled_from(["f32", "bf16", "s8"]))
    @settings(max_examples=20, deadline=None)
    def test_shape_bytes_property(self, n, dt):
        per = {"f32": 4, "bf16": 2, "s8": 1}[dt]
        assert roofline.shape_bytes(f"{dt}[{n}]") == n * per


class TestInputSpecs:
    @pytest.mark.parametrize("name", sorted(ARCHS))
    def test_all_cells_have_structs(self, name):
        cfg = ARCHS[name]
        api = build_model(cfg)
        for sname, shape in SHAPES.items():
            ok, _ = shape_applicable(cfg, shape)
            if not ok:
                continue
            if shape.kind == "train":
                b = train_batch_specs(cfg, shape)
                assert b["tokens"].shape == (shape.global_batch,
                                             shape.seq_len)
            elif shape.kind == "prefill":
                b = prefill_batch_specs(cfg, shape)
                assert b["tokens"].shape[0] == shape.global_batch
            else:
                c = cache_structs(api, shape.global_batch, shape.seq_len)
                assert jax.tree.leaves(c)   # non-empty, no allocation

    def test_params_structs_no_allocation(self):
        api = build_model(ARCHS["qwen2-72b"])
        tree = params_structs(api)
        total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
        # ~72B params represented abstractly (nothing allocated)
        assert total > 60e9
        assert all(isinstance(l, jax.ShapeDtypeStruct)
                   for l in jax.tree.leaves(tree))


class TestModelFlops:
    def test_train_flops_formula(self):
        cfg = ARCHS["qwen2.5-3b"]
        shape = SHAPES["train_4k"]
        mf = roofline.model_flops_for(cfg, shape)
        assert mf == pytest.approx(6 * cfg.params_count()
                                   * 256 * 4096, rel=1e-6)

    def test_moe_uses_active_params(self):
        cfg = ARCHS["mixtral-8x22b"]
        assert cfg.active_params_count() < 0.45 * cfg.params_count()


@pytest.mark.parametrize("env_dir", [None, "/shared/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
    otherwise the cache sits at the fixed ``<repo>/.jax_cache``."""
    from repro.launch import compile_cache
    old = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        path = compile_cache.enable()
        if env_dir is None:
            assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
            assert (compile_cache.REPO_ROOT / "chip_smoke.py").exists()
            assert jax.config.jax_compilation_cache_dir == path
        else:
            assert path == env_dir
            assert jax.config.jax_compilation_cache_dir == old
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
