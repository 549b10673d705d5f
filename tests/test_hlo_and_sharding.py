"""HLO analyzer correctness + partition-rule sanity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_mesh


class TestHloAnalysis:
    def test_scan_trip_count_multiplication(self):
        d = 64

        def f(x, ws):
            y, _ = jax.lax.scan(lambda x, w: (x @ w, ()), x, ws)
            return y

        c = jax.jit(f).lower(jnp.zeros((d, d)), jnp.zeros((12, d, d))).compile()
        costs = H.analyze(c.as_text())
        assert costs.flops == pytest.approx(12 * 2 * d ** 3, rel=0.01)

    def test_nested_scan(self):
        d = 32

        def inner(x, ws):
            y, _ = jax.lax.scan(lambda x, w: (x @ w, ()), x, ws)
            return y

        def outer(x, ws):
            y, _ = jax.lax.scan(lambda x, _: (inner(x, ws), ()), x, None,
                                length=3)
            return y

        c = jax.jit(outer).lower(jnp.zeros((d, d)),
                                 jnp.zeros((5, d, d))).compile()
        costs = H.analyze(c.as_text())
        assert costs.flops == pytest.approx(3 * 5 * 2 * d ** 3, rel=0.02)

    def test_unsharded_matmul_flops_and_bytes(self):
        # f32: the CPU backend would wrap bf16 dots in f32 converts
        m, k, n = 128, 256, 64
        c = jax.jit(jnp.dot).lower(jnp.zeros((m, k), jnp.float32),
                                   jnp.zeros((k, n), jnp.float32)).compile()
        costs = H.analyze(c.as_text())
        assert costs.flops == pytest.approx(2 * m * k * n, rel=0.01)
        want_bytes = 4 * (m * k + k * n + m * n)
        assert costs.hbm_bytes == pytest.approx(want_bytes, rel=0.25)

    def test_collective_wire_formulas(self):
        assert H._collective_wire_bytes("all-gather", 100, 25, 4) == 75
        assert H._collective_wire_bytes("all-reduce", 100, 100, 4) == 150
        assert H._collective_wire_bytes("reduce-scatter", 25, 100, 4) == 75
        assert H._collective_wire_bytes("collective-permute", 50, 50, 4) == 50
        assert H._collective_wire_bytes("all-reduce", 100, 100, 1) == 0

    def test_comment_stripping(self):
        comps = H.split_computations(
            "ENTRY %e (p: (f32[2], /*index=1*/f32[3])) -> f32[2] {\n"
            "  ROOT %r = f32[2]{0} add(%a, %b)\n}\n")
        assert "__entry__" in comps


class TestShardingRules:
    def setup_method(self):
        # a tiny mesh stands in: rules only read axis names/sizes
        self.mesh = make_mesh((1, 1), ("data", "model"))

    def test_param_rules(self):
        from repro.distributed import sharding as SH
        spec = SH.param_spec("stages/0/0/attn/wq/w", (512, 512), self.mesh)
        assert spec == P(("data",), "model")
        spec = SH.param_spec("stages/0/0/ffn/down/w", (2048, 512), self.mesh)
        assert spec == P("model", ("data",))
        spec = SH.param_spec("stages/0/0/ffn/experts/gate/w",
                             (64, 512, 128), self.mesh)
        assert spec == P("model", ("data",), None)
        spec = SH.param_spec("final_norm/scale", (512,), self.mesh)
        assert spec == P()

    def test_factorized_rules(self):
        # perf iteration C4 layout: col-type v rank-split over model;
        # row-type u out-split over model
        from repro.distributed import sharding as SH
        assert SH.param_spec("stages/0/0/attn/wq/v", (512, 64), self.mesh) \
            == P(("data",), "model")
        assert SH.param_spec("stages/0/0/attn/wq/u", (64, 512), self.mesh) \
            == P(None, "model")
        assert SH.param_spec("stages/0/0/ffn/down/v", (2048, 64), self.mesh) \
            == P("model", ("data",))
        assert SH.param_spec("stages/0/0/ffn/down/u", (64, 512), self.mesh) \
            == P(("data",), "model")

    def test_indivisible_dims_fall_back_to_replication(self):
        from repro.distributed import sharding as SH
        mesh = make_mesh((1, 1), ("data", "model"))
        # simulate 16-way axis via a fake check: use mesh with size 1 -> all
        # dims divide; instead check _fit drops non-dividing axes
        spec = SH._fit(mesh, ["model", None], (7, 8))
        assert spec == P("model", None)   # 7 % 1 == 0 trivially

    def test_cache_shardings_structure(self):
        from repro.configs import get_smoke_config
        from repro.distributed import sharding as SH
        from repro.models import model as M
        cfg = get_smoke_config("gemma3-1b")
        cache = M.init_cache(cfg, 2, 32)
        sh = SH.cache_shardings(cache, cfg, self.mesh)
        assert jax.tree_util.tree_structure(
            jax.tree.map(lambda x: 0, cache)) == \
            jax.tree_util.tree_structure(jax.tree.map(lambda x: 0, sh))
