"""Every Pallas kernel compiles for a TPU v5e at real widths, and the
whitened solve lowers to the TPU's Jacobi eigensolver.

The kernels go through ``repro.kernels.ops`` (``force_pallas=True``, so
the CPU backend's reference dispatch is bypassed) and are compiled ahead
of time for one chip of a described ``v5e:2x2`` topology — the TPU
compiler rejects what interpret mode accepts (block shapes off the (8, 128)
tiling, scoped-memory overruns).  Widths are qwen3-0.6b's (d_model 1024,
16 heads / 8 KV heads of 128, d_ff 3072) over 4096 calibration tokens;
``grouped_matmul`` uses deepseek-v2-lite's expert widths (2048 → 1408,
16 experts), and ``flash_decode_stacked`` granite-3-8b's stacked latent
cache.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lowrank as LR
from repro.kernels import ops

T = 4096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001; repro-check: allow[bare-except] — any failure to describe the chip is a skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such compiles cannot be read back from the persistent cache without
    # a chip; keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _cases(dtype):
    def sds(*shape, dt=dtype):
        return (shape, dt)

    b, h, kv, d, l, r = 8, 16, 8, 128, 2048, 256
    cases = {
        f"cov_accum_n{n}": (
            lambda x, y: ops.cov_accum(x, y, force_pallas=True),
            [sds(T, n), sds(T, n)])
        for n in (1024, 2048, 3072)
    }
    cases["lowrank_matmul"] = (
        lambda x, v, u: ops.lowrank_matmul(x, v, u, force_pallas=True),
        [sds(T, 1024), sds(1024, 464), sds(464, 3072)])
    cases["lowrank_matmul_epilogue"] = (
        lambda x, v, u, bias, res: ops.lowrank_matmul(
            x, v, u, bias=bias, residual=res, force_pallas=True),
        [sds(T, 3072), sds(3072, 464), sds(464, 1024), sds(1024),
         sds(T, 1024)])
    cases["grouped_matmul"] = (
        lambda x, w, g: ops.grouped_matmul(x, w, g, force_pallas=True),
        [sds(T, 2048), sds(16, 2048, 1408), sds(16, dt=jnp.int32)])
    cases["flash_attention"] = (
        lambda q, k, v: ops.flash_attention(q, k, v, force_pallas=True),
        [sds(1, h, l, d), sds(1, kv, l, d), sds(1, kv, l, d)])
    cases["flash_decode"] = (
        lambda q, lk, lv, uk, uv, lens, c, s, kn, vn: ops.flash_decode(
            q, lk, lv, uk, uv, lens, c, s, kn, vn, force_pallas=True),
        [sds(b, h, d), sds(b, r, l), sds(b, r, l), sds(r, kv * d),
         sds(r, kv * d), sds(b, dt=jnp.int32), sds(l, d // 2),
         sds(l, d // 2), sds(b, r), sds(b, r)])
    # granite-3-8b's serving shape: the stacked rank-major latent cache of
    # 20 layers, 64 slots and 2048 positions at rank 496 (off the lane
    # grid), read at a layer index
    n, gb, gh, gr = 20, 64, 32, 496
    cases["flash_decode_stacked"] = (
        lambda q, lk, lv, uk, uv, lens, c, s, kn, vn, i: ops.flash_decode(
            q, lk, lv, uk, uv, lens, c, s, kn, vn, layer=i,
            force_pallas=True),
        [sds(gb, gh, d), sds(n, gb, gr, l), sds(n, gb, gr, l),
         sds(gr, kv * d), sds(gr, kv * d), sds(gb, dt=jnp.int32),
         sds(l, d // 2), sds(l, d // 2), sds(gb, gr), sds(gb, gr),
         sds(dt=jnp.int32)])
    return cases


KERNELS = sorted(_cases(jnp.float32))

N = 3072        # d_ff: the widest input covariance of qwen3-0.6b
SOLVES = {
    "solve_anchored": lambda w, c: LR.solve_anchored(w, c, c, 464),
    "solve_agnostic": lambda w, c: LR.solve_agnostic(w, 464),
    "whitened_spectrum": lambda w, c: LR.whitened_spectrum(w, c, c),
    "weight_spectrum": lambda w, c: LR.weight_spectrum(w),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype):
    fn, shapes = _cases(dtype)[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{kernel}: the compiled program holds no Pallas kernel")


@pytest.mark.parametrize("solve", sorted(SOLVES))
def test_solve_lowers_to_jacobi_eigh_on_tpu(one_chip, solve):
    """The whitened solve's decompositions lower to the TPU's native Jacobi
    ``Eigh``, which compiles in seconds; the default QDWH ``eigh``/``svd``
    (while-loop divide and conquer) takes minutes per shape at these
    widths.  Lowering only — no compile."""
    args = [jax.ShapeDtypeStruct((N, 1024), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((N, N), jnp.float32, sharding=one_chip)]
    text = jax.jit(SOLVES[solve]).lower(*args).as_text()
    assert "@Eigh" in text
    assert "stablehlo.while" not in text


def test_stacked_flash_decode_reads_cache_in_place(one_chip):
    """At granite-3-8b's stacked latent cache the TPU compiler gives
    ``flash_decode`` the cache as it lies: the operands alias the outputs
    and no temporary holds even one layer's copy (a token-major cache,
    laid out rank-minor by the TPU, was transposed whole on every call)."""
    fn, shapes = _cases(jnp.bfloat16)["flash_decode_stacked"]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    mem = jax.jit(fn, donate_argnums=(1, 2)).lower(
        *args).compile().memory_analysis()
    n, b, r, l = shapes[1][0]
    assert mem.alias_size_in_bytes == 2 * n * b * r * l * 2
    assert mem.temp_size_in_bytes < b * r * l * 2
