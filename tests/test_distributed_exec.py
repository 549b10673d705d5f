"""Multi-device execution tests (subprocess: 8 virtual CPU devices).

The main test session pins JAX to one device (conftest), so the shard_map
paths — expert parallelism, decode-EP, sequence-parallel flash decode — are
exercised in a child interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  Each script asserts
numerical equivalence against the single-device reference and prints OK.
"""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# composed-map comparison for refined params: the whitened solve carries a
# per-direction scale gauge (u row × α, v column × 1/α) that fp32
# covariance jitter can flip near degenerate singular values — the linear
# map each factor pair represents is the DP-invariant quantity
_COMPARE_REFINED = """
assert (jax.tree_util.tree_structure(ref_p)
        == jax.tree_util.tree_structure(dp_p))
n_pairs = 0
def close(a, b, path):
    np.testing.assert_allclose(
        b, a, rtol=2e-3, atol=2e-3 * max(np.abs(a).max(), 1.0),
        err_msg=path)
def compare(t1, t8, path):
    global n_pairs
    if isinstance(t1, dict):
        if "u" in t1 and "v" in t1:
            n_pairs += 1
            close(np.matmul(np.asarray(t1["v"]), np.asarray(t1["u"])),
                  np.matmul(np.asarray(t8["v"]), np.asarray(t8["u"])),
                  path + "(v@u)")
            rest = [k for k in t1 if k not in ("u", "v")]
        else:
            rest = list(t1)
        for k in rest:
            compare(t1[k], t8[k], f"{path}/{k}")
    elif isinstance(t1, (list, tuple)):
        for i, (x, y) in enumerate(zip(t1, t8)):
            compare(x, y, f"{path}[{i}]")
    else:
        close(np.asarray(t1), np.asarray(t8), path)
compare(ref_p, dp_p, "")
assert n_pairs > 0
print("OK")
"""


def run_child(script: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "OK" in out.stdout, out.stdout


COMMON = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.distributed import sharding as SH
from repro.launch.mesh import make_mesh
"""


def test_moe_expert_parallel_equivalence():
    run_child(COMMON + """
from repro.models import mlp
cfg = get_smoke_config("deepseek-v2-lite-16b").replace(dtype="float32")
p = mlp.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 128, cfg.d_model)) * 0.5
y_ref, aux_ref = mlp.moe_apply(p, x, cfg, capacity_factor=64.0)
mesh = make_mesh((2, 4), ("data", "model"))
def f(p, x):
    with SH.use_mesh(mesh, cfg=cfg):
        return mlp.moe_apply(p, x, cfg, capacity_factor=64.0)
y, aux = jax.jit(f)(p, x)
np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
assert abs(float(aux) - float(aux_ref)) < 1e-6
print("OK")
""")


def test_moe_decode_ep_equivalence():
    run_child(COMMON + """
from repro.models import mlp
cfg = get_smoke_config("deepseek-v2-lite-16b").replace(dtype="float32")
p = mlp.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, cfg.d_model)) * 0.5
y_ref, aux_ref = mlp.moe_apply(p, x, cfg, capacity_factor=64.0)
mesh = make_mesh((2, 4), ("data", "model"))
def f(p, x):
    with SH.use_mesh(mesh, cfg=cfg):
        return mlp.moe_apply(p, x, cfg, capacity_factor=64.0)
y, aux = jax.jit(f)(p, x)
np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
print("OK")
""")


def test_moe_expert_parallel_equivalence_dropfree():
    """Drop-free dispatch under the EP mesh: every rank routes the
    all-gathered tokens identically, computes its local experts' ragged
    segments via the grouped GEMM, and one psum combines — must match the
    single-device drop-free forward exactly (nothing drops, so no
    capacity_factor headroom is needed)."""
    run_child(COMMON + """
from repro.models import mlp
cfg = get_smoke_config("deepseek-v2-lite-16b").replace(dtype="float32")
p = mlp.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 128, cfg.d_model)) * 0.5
y_ref, aux_ref = mlp.moe_apply(p, x, cfg, dispatch="dropfree")
mesh = make_mesh((2, 4), ("data", "model"))
def f(p, x):
    with SH.use_mesh(mesh, cfg=cfg):
        return mlp.moe_apply(p, x, cfg, dispatch="dropfree")
y, aux = jax.jit(f)(p, x)
np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
assert abs(float(aux) - float(aux_ref)) < 1e-6
print("OK")
""")


def test_moe_decode_ep_equivalence_dropfree():
    run_child(COMMON + """
from repro.models import mlp
cfg = get_smoke_config("deepseek-v2-lite-16b").replace(dtype="float32")
p = mlp.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, cfg.d_model)) * 0.5
y_ref, aux_ref = mlp.moe_apply(p, x, cfg, dispatch="dropfree")
mesh = make_mesh((2, 4), ("data", "model"))
def f(p, x):
    with SH.use_mesh(mesh, cfg=cfg):
        return mlp.moe_apply(p, x, cfg, dispatch="dropfree")
y, aux = jax.jit(f)(p, x)
np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
print("OK")
""")


def test_seqpar_flash_decode_equivalence():
    run_child(COMMON + """
from repro.models import attention as A
from repro.configs.base import ModelConfig
cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                  num_heads=8, num_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=100)
key = jax.random.PRNGKey(0)
B, L, KV, D, H = 4, 64, 2, 16, 8
q = jax.random.normal(key, (B, 1, H, D), jnp.float32)
k = jax.random.normal(jax.random.PRNGKey(1), (B, L, KV, D), jnp.float32)
v = jax.random.normal(jax.random.PRNGKey(2), (B, L, KV, D), jnp.float32)
for pos in (0, 17, 63):
    ref = A.flash_attention(q, k, v, causal=True, q_offset=pos, chunk=16)
    mesh = make_mesh((2, 4), ("data", "model"))  # KV=2 % 4 != 0 -> seqpar
    def f(q, k, v):
        with SH.use_mesh(mesh, cfg=cfg):
            return A._decode_attention(q, k, v, pos, cfg, chunk=16)
    out = jax.jit(f)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
print("OK")
""")


def test_sharded_train_step_runs_and_matches_unsharded_loss():
    run_child(COMMON + """
from repro.data import make_batch_iterator
from repro.launch import steps as S
cfg = get_smoke_config("granite-3-8b").replace(dtype="float32")
batch = next(make_batch_iterator(cfg, 4, 32, seed=0))
mesh = make_mesh((2, 4), ("data", "model"))
state_struct = jax.eval_shape(lambda: S.init_train_state(cfg, jax.random.PRNGKey(0)))
state_sh, batch_sh = S.train_shardings(cfg, mesh, state_struct,
                                       jax.eval_shape(lambda: batch))
jstep = jax.jit(S.make_train_step(cfg, mesh),
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None), donate_argnums=(0,))
state = jax.jit(lambda k: S.init_train_state(cfg, k),
                out_shardings=state_sh)(jax.random.PRNGKey(0))
state, metrics = jstep(state, batch)
loss_sharded = float(metrics["loss"])

# unsharded reference
step1 = jax.jit(S.make_train_step(cfg, None))
st = S.init_train_state(cfg, jax.random.PRNGKey(0))
_, m1 = step1(st, batch)
assert abs(loss_sharded - float(m1["loss"])) < 2e-3, (loss_sharded, float(m1["loss"]))
print("OK")
""")


def test_sharded_fused_cov_matches_unsharded_fused():
    """The SPMD cov path: under ``calib_mesh`` the wrappers shard_map the
    FUSED Pallas kernel (forced, interpret) over the data axes — per-worker
    partial triples + one psum — and must match the unsharded fused path to
    fp32 tolerance, on token counts not divisible by the DP degree and
    unaligned feature dims.  Covers both the flat and the expert-bank
    entry points (there is no einsum fallback branch anymore)."""
    run_child(COMMON + """
from repro.kernels import ops, ref
from repro.launch.mesh import make_calib_mesh

mesh = make_calib_mesh()
assert dict(mesh.shape) == {"data": 8}, mesh
k1, k2 = jax.random.split(jax.random.PRNGKey(0))

def check(outs, wants, label):
    for o, w in zip(outs, wants):
        a, b = np.asarray(o), np.asarray(w)
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-4 * max(np.abs(b).max(), 1.0),
            err_msg=label)

# flat: 1000 rows (not divisible by 8), n=100 (not lane-aligned)
x = jax.random.normal(k1, (1000, 100), jnp.float32)
xp = x + 0.1 * jax.random.normal(k2, (1000, 100), jnp.float32)
dp = ops.cov_accum(x, xp, mesh=mesh, force_pallas=True, interpret=True)
un = ops.cov_accum(x, xp, force_pallas=True, interpret=True)
check(dp, un, "flat dp-vs-unsharded")
check(dp, ref.cov_accum_ref(x, xp), "flat dp-vs-ref")

# accumulate-into under the mesh
acc = tuple(jnp.ones((100, 100), jnp.float32) for _ in range(3))
dp_acc = ops.cov_accum(x, xp, acc=acc, mesh=mesh,
                       force_pallas=True, interpret=True)
check(dp_acc, tuple(a + o for a, o in zip(acc, un)), "flat acc")

# banked: capacity 130 (not divisible by 8), n=72 unaligned
xb = jax.random.normal(k1, (3, 130, 72), jnp.float32)
xpb = xb + 0.1 * jax.random.normal(k2, (3, 130, 72), jnp.float32)
dpb = ops.cov_accum_banked(xb, xpb, mesh=mesh,
                           force_pallas=True, interpret=True)
check(dpb, ops.cov_accum_banked(xb, xpb, force_pallas=True,
                                interpret=True), "banked dp-vs-unsharded")
check(dpb, ref.cov_accum_banked_ref(xb, xpb), "banked dp-vs-ref")
print("OK")
""")


def test_sharded_calibration_dp_invariance():
    """CompressConfig.calib_mesh shards stage-1 collection over 8 DP
    workers: covariance triples and final compressed params must match the
    unsharded run to fp32 tolerance, with per-device tapped forwards
    reduced by the DP degree."""
    run_child(COMMON + """
import dataclasses
from repro.core import CompressConfig, compress_model
from repro.data import calibration_set
from repro.launch.mesh import make_calib_mesh
from repro.models import model as M

cfg = get_smoke_config("llama-7b").replace(dtype="float32")
params = M.init_params(cfg, jax.random.PRNGKey(0))
calib = calibration_set(cfg, 16, 32)
base = CompressConfig(ratio=0.6, refine=False, rank_multiple=1,
                      microbatch=2, calib_mode="fused", debug_covs=True)
ref_p, rep1 = compress_model(params, cfg, calib, base)
mesh = make_calib_mesh()
assert dict(mesh.shape) == {"data": 8}, mesh
dp_p, rep8 = compress_model(params, cfg, calib,
                            dataclasses.replace(base, calib_mesh=mesh))

# per-device tapped forwards reduced by the DP degree
assert rep8["calibration"]["calib_dp"] == 8
assert rep1["calibration"]["calib_dp"] == 1
assert (rep8["calibration"]["tapped_forwards"] * 8
        == rep1["calibration"]["tapped_forwards"]), (
    rep1["calibration"], rep8["calibration"])

# covariance triples match to fp32 tolerance
checked = 0
for u1, u8 in zip(rep1["units"], rep8["units"]):
    for tap, c1 in u1.get("covs", {}).items():
        c8 = u8["covs"][tap]
        for key in ("xx", "xxp", "xpxp", "count"):
            a, b = np.asarray(c1[key]), np.asarray(c8[key])
            np.testing.assert_allclose(
                b, a, rtol=2e-4, atol=2e-4 * max(np.abs(a).max(), 1.0),
                err_msg=f"{u1['name']}/{tap}/{key}")
            checked += 1
assert checked > 0

# final compressed params match to fp32 tolerance
l1, d1 = jax.tree_util.tree_flatten(ref_p)
l8, d8 = jax.tree_util.tree_flatten(dp_p)
assert d1 == d8
for i, (a, b) in enumerate(zip(l1, l8)):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(
        b, a, rtol=2e-3, atol=2e-3 * max(np.abs(a).max(), 1.0),
        err_msg=f"leaf {i}")
print("OK")
""")


def test_sharded_calibration_dp_invariance_dropfree_banks():
    """The headline unlock of drop-free routing: bank-bearing MoE units
    FOLD their dp microbatches into one calibration forward.  Under
    capacity dispatch this is illegal (routing depends on batch size), so
    the engine pinned MoE units to per-microbatch forwards; the grouped
    (T·k, d) layout is exactly batch-size-invariant, so folding is legal
    and the folded run must reproduce the unsharded covariance triples and
    compressed params.

    Factor pairs are compared as composed v@u maps: at smoke scale
    deepseek's per-expert covariances are barely full-rank (~256 routed
    rows per expert against n=64), and the whitened solve's scale gauge
    flips under that jitter while the composed map stays put (same
    rationale as ``_COMPARE_REFINED``)."""
    run_child(COMMON + """
import dataclasses
from repro.core import CompressConfig, compress_model
from repro.data import calibration_set
from repro.launch.mesh import make_calib_mesh
from repro.models import model as M

cfg = get_smoke_config("deepseek-v2-lite-16b").replace(dtype="float32")
params = M.init_params(cfg, jax.random.PRNGKey(0))
# 64-token sequences keep every expert's covariance well-conditioned
# (~256 rows per expert vs n=64); shorter calib makes the comparison
# measure stage-1 solve jitter instead of the folding under test
calib = calibration_set(cfg, 16, 64)
base = CompressConfig(ratio=0.6, refine=False, rank_multiple=1,
                      microbatch=2, calib_mode="fused", debug_covs=True,
                      moe_dispatch="dropfree")
ref_p, rep1 = compress_model(params, cfg, calib, base)
mesh = make_calib_mesh()
assert dict(mesh.shape) == {"data": 8}, mesh
dp_p, rep8 = compress_model(params, cfg, calib,
                            dataclasses.replace(base, calib_mesh=mesh))

assert rep8["calibration"]["calib_dp"] == 8
assert rep8["calibration"]["moe_dispatch"] == "dropfree"
# EVERY unit folded — including the bank-bearing MoE unit
assert (rep8["calibration"]["tapped_forwards"] * 8
        == rep1["calibration"]["tapped_forwards"]), (
    rep1["calibration"], rep8["calibration"])
moe1 = [u for u in rep1["units"] if u["kind"].endswith("_moe")]
moe8 = [u for u in rep8["units"] if u["kind"].endswith("_moe")]
assert moe1 and moe8
for u1, u8 in zip(moe1, moe8):
    assert u8["tapped_forwards"] * 8 == u1["tapped_forwards"], (u1, u8)
    assert u8["moe_drop_rate"] == 0.0

# covariance triples — per-expert (E, n, n) banks included — match
checked_banks = 0
for u1, u8 in zip(rep1["units"], rep8["units"]):
    for tap, c1 in u1.get("covs", {}).items():
        c8 = u8["covs"][tap]
        for key in ("xx", "xxp", "xpxp", "count"):
            a, b = np.asarray(c1[key]), np.asarray(c8[key])
            np.testing.assert_allclose(
                b, a, rtol=2e-4, atol=2e-4 * max(np.abs(a).max(), 1.0),
                err_msg=f"{u1['name']}/{tap}/{key}")
            if a.ndim == 3:
                checked_banks += 1
assert checked_banks > 0

# compressed params match as composed maps
""" + _COMPARE_REFINED)


def test_sharded_refinement_dp_invariance():
    """Stage-2 refinement under ``calib_mesh``: the scanned refinement
    sweep shards each step's microbatch over 8 DP workers (params/optimizer
    carry replicated, per-worker grads + one psum per step — never folding
    steps), so refined params and post-refine MSE must match the unsharded
    run to fp32 tolerance (factor pairs as composed maps, see
    ``_COMPARE_REFINED``)."""
    run_child(COMMON + """
import dataclasses
from repro.core import CompressConfig, compress_model
from repro.data import calibration_set
from repro.launch.mesh import make_calib_mesh
from repro.models import model as M

cfg = get_smoke_config("llama-7b").replace(dtype="float32")
params = M.init_params(cfg, jax.random.PRNGKey(0))
calib = calibration_set(cfg, 16, 32)
# microbatch 8: each refinement step's batch dim (8 sequences) shards 8-way
base = CompressConfig(ratio=0.6, rank_multiple=1, microbatch=8,
                      calib_mode="fused", refine_epochs=3)
ref_p, rep1 = compress_model(params, cfg, calib, base)
mesh = make_calib_mesh()
assert dict(mesh.shape) == {"data": 8}, mesh
dp_p, rep8 = compress_model(params, cfg, calib,
                            dataclasses.replace(base, calib_mesh=mesh))

# refinement ran scanned on both sides, same optimizer schedule
checked = 0
for u1, u8 in zip(rep1["units"], rep8["units"]):
    if "post_refine_mse" not in u1:
        continue
    assert u1["refine_mode"] == u8["refine_mode"] == "scan", (u1, u8)
    assert u1["refine_steps"] == u8["refine_steps"]
    np.testing.assert_allclose(
        u8["post_refine_mse"], u1["post_refine_mse"], rtol=5e-3,
        err_msg=u1["name"])
    checked += 1
assert checked > 0

# refined params match the unsharded run to fp32 tolerance
""" + _COMPARE_REFINED)


@pytest.mark.slow
def test_sharded_refinement_dp_invariance_expert_banks():
    """The bank-bearing case of the invariance above (PR 3 found a real
    bank DP bug in this dispatch layer): refinement steps are never
    folded, so the batch-size-dependent capacity routing sees the same
    global microbatch and a routed MoE unit refines DP-invariantly.

    Stage 2 is isolated from stage 1 here — the engine refines the SAME
    deepseek MoE unit params meshed and unmeshed (deepseek's per-expert
    covariances at smoke scale are near-singular, so an end-to-end
    compressed comparison would measure stage-1 solve jitter, not the
    refinement engine)."""
    run_child(COMMON + """
from repro.core import pipeline as P
from repro.core import refine as RF
from repro.data import calibration_set
from repro.launch.mesh import make_calib_mesh
from repro.models import model as M

cfg = get_smoke_config("deepseek-v2-lite-16b").replace(dtype="float32")
params = M.init_params(cfg, jax.random.PRNGKey(0))
calib = calibration_set(cfg, 16, 16)
moe = [u for u in P.unroll_units(params, cfg)
       if u.kind.endswith("_moe")][0]
fwd = P.make_unit_apply(moe.kind, cfg, 16, want_taps=False)
xs = P._embed_stream(params, cfg, calib, 8)   # 2 microbatches of 8: the
# per-step batch dim shards 8-way under the mesh
ys = [fwd(moe.params, x, None) for x in xs]
start = jax.tree.map(lambda a: a * 1.1, moe.params)
xp_b = [(x, None) for x in xs]
out1, h1 = RF.refine_unit(fwd, start, xp_b, ys, epochs=3, lr=1e-4,
                          scan=True)
out8, h8 = RF.refine_unit(fwd, start, xp_b, ys, epochs=3, lr=1e-4,
                          scan=True, mesh=make_calib_mesh())
assert h1["mode"] == h8["mode"] == "scan"
assert h1["steps"] == h8["steps"] == 6
assert h1["post_refine_mse"] < h1["pre_refine_mse"]
np.testing.assert_allclose(h8["post_refine_mse"], h1["post_refine_mse"],
                           rtol=5e-3)
l1, d1 = jax.tree_util.tree_flatten(out1)
l8, d8 = jax.tree_util.tree_flatten(out8)
assert d1 == d8
for i, (a, b) in enumerate(zip(l1, l8)):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(
        b, a, rtol=2e-3, atol=2e-3 * max(np.abs(a).max(), 1.0),
        err_msg=f"leaf {i}")
print("OK")
""")


def test_compressed_serve_step_sharded():
    run_child(COMMON + """
from repro.core.factorized import factorize_params
from repro.launch import steps as S
from repro.models import model as M
cfg = get_smoke_config("llama-7b").replace(dtype="float32",
                                           compress_ratio=0.6)
params = M.init_params(cfg, jax.random.PRNGKey(0))
params = factorize_params(params, cfg, rank_multiple=4)
cache = M.init_cache(cfg, 4, 32)
mesh = make_mesh((2, 4), ("data", "model"))
psh, csh = S.decode_shardings(cfg, mesh, jax.eval_shape(lambda: params),
                              jax.eval_shape(lambda: cache))
step = jax.jit(S.make_serve_step(cfg, mesh), in_shardings=(
    psh, csh, None, None), out_shardings=(None, csh), donate_argnums=(1,))
tok = jnp.zeros((4, 1), jnp.int32)
next_tok, cache = step(params, cache, tok, 0)
assert next_tok.shape == (4, 1)
assert int(next_tok.min()) >= 0 and int(next_tok.max()) < cfg.vocab_size
print("OK")
""")


def test_expert_parallel_server_matches_one_device():
    """Server under a (1, 4) mesh: params initialized in their serving
    layout hold 1/4 of each expert bank per device (the bank's expert axis,
    not its layer-stack axis, is split), and greedy decode matches the same
    model served on one device token for token (drop-free dispatch is
    independent of how tokens are grouped)."""
    run_child(COMMON + """
import dataclasses
from repro.launch.serve import Server
from repro.models import model as M
base = get_smoke_config("deepseek-v2-lite-16b")
cfg = base.replace(num_layers=3, dtype="float32",
                   moe=dataclasses.replace(base.moe, dispatch="dropfree"))
key = jax.random.PRNGKey(0)
devs = jax.devices()
prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                             cfg.vocab_size)
outs = []
for mesh in (make_mesh((1, 1), ("data", "model"), devices=devs[:1]),
             make_mesh((1, 4), ("data", "model"), devices=devs[:4])):
    shapes = jax.eval_shape(lambda: M.init_params(cfg, key))
    psh = SH.param_shardings(shapes, mesh, mode="serve", cfg=cfg)
    params = jax.jit(lambda: M.init_params(cfg, key), out_shardings=psh)()
    srv = Server(cfg, params, max_len=80, batch=4, mesh=mesh)
    outs.append(np.asarray(srv.generate(prompts, steps=8)))
bank = srv.params["stages"][1][0]["ffn"]["experts"]["gate"]["w"]
e = cfg.moe.num_experts
assert bank.shape[:2] == (2, e), bank.shape
assert {s.data.shape[:2] for s in bank.addressable_shards} == {(2, e // 4)}
np.testing.assert_array_equal(outs[0], outs[1])
print("OK")
""")
