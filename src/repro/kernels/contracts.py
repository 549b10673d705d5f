"""Static contracts for every registered Pallas kernel.

One :class:`KernelContract` per entry in ``autotune._LATTICES`` describes,
without touching hardware, what the dispatch wrapper + autotuner pair must
guarantee:

* **alignment** — each block dim's tile multiple, exactly as the lattice
  filters it (``_pick_valid``'s ``lane`` argument): sublane-tiled dims are
  8-multiples, lane-tiled dims 128-multiples (fp32 Mosaic min tile 8×128);
* **VMEM fit** — every candidate's double-buffered working set stays
  inside the autotuner budget (candidates are born filtered; the contract
  re-checks so a lattice edit can't silently outgrow the model);
* **abstract evaluability** — for each candidate, mirror the ``ops.py``
  wrapper's lane/block padding and ``jax.eval_shape`` the *real* kernel:
  ``pallas_call`` traces the kernel body and validates grid/BlockSpec/
  index-map consistency at bind time, so a bad block shape fails here, in
  the checker, instead of in Mosaic at runtime — and the traced output
  shapes must equal :meth:`KernelContract.expected`.

Probes deliberately include unaligned problem shapes (the 80-dim whisper
tap, ragged token counts) because the padding arithmetic is exactly where
the historical bugs lived.  ``repro.analysis.contracts`` drives these;
this module only declares them (it lives in ``kernels/`` so a new kernel
lands next to its contract and the registry check can't be forgotten).
"""

from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.cov_accum import cov_accum as _cov_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.flash_decode import flash_decode as _decode_kernel
from repro.kernels.grouped_matmul import grouped_matmul as _grouped_kernel
from repro.kernels.lowrank_matmul import lowrank_matmul as _lowrank_kernel

_LANE = autotune._LANE          # 128
_SUBLANE = 8


def _ru(x: int, m: int) -> int:
    return -(-x // m) * m


def _rl(x: int) -> int:
    return _ru(x, _LANE)


class KernelContract(NamedTuple):
    """Static contract for one kernel's (lattice, wrapper, kernel) triple.

    ``align``     block-dim name -> required multiple (8 sublane / 128
                  lane), mirroring the lattice's ``_pick_valid`` calls.
    ``probes``    problem-shape dicts covering aligned AND unaligned dims.
    ``candidates``(probe) -> the autotuner's candidate list for the probe.
    ``abstract_eval``(probe, blocks) -> traced output
                  ``jax.ShapeDtypeStruct``s of the real kernel under the
                  wrapper's padding (raises if the kernel rejects the
                  blocks — that IS the check).
    ``expected``  (probe, blocks) -> the output shapes the wrapper relies
                  on when slicing back to caller shapes.
    """

    name: str
    align: Dict[str, int]
    probes: Tuple[Dict[str, int], ...]
    candidates: Callable[[Dict[str, int]], List[autotune.Candidate]]
    abstract_eval: Callable[[Dict[str, int], Dict[str, int]], tuple]
    expected: Callable[[Dict[str, int], Dict[str, int]], tuple]


def _struct(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# cov_accum — fused single-pass covariance triple on (T, n) token rows


def _cov_dims(p, blocks):
    tp = _ru(p["t"], blocks["bt"])
    np_ = _ru(_rl(p["n"]), blocks["bi"])       # lane-pad, then block-pad
    return tp, np_


def _cov_abstract(p, blocks):
    tp, np_ = _cov_dims(p, blocks)
    x = _struct((tp, np_))
    return jax.eval_shape(
        lambda a, b: _cov_kernel(a, b, bi=blocks["bi"], bt=blocks["bt"]),
        x, x)


def _cov_expected(p, blocks):
    _, np_ = _cov_dims(p, blocks)
    return tuple(_struct((np_, np_)) for _ in range(3))


_COV = KernelContract(
    name="cov_accum",
    align={"bt": _SUBLANE, "bi": _LANE},
    probes=(
        {"t": 1024, "n": 512},     # aligned (the transformer tap shape)
        {"t": 300, "n": 80},       # ragged tokens + the 80-dim whisper tap
        {"t": 8, "n": 128},        # minimum-tile degenerate case
    ),
    candidates=lambda p: autotune.cov_candidates(p["t"], _rl(p["n"])),
    abstract_eval=_cov_abstract,
    expected=_cov_expected,
)


# ---------------------------------------------------------------------------
# lowrank_matmul — phase-fused (x @ V) @ U with optional epilogue


def _lr_dims(p, blocks):
    tp = _ru(p["t"], blocks["bt"])
    np_ = _ru(_rl(p["n"]), blocks["bn"])
    kl = _rl(p["k"])
    mp = _ru(_rl(p["m"]), blocks["bm"])
    return tp, np_, kl, mp


def _lr_abstract(p, blocks):
    tp, np_, kl, mp = _lr_dims(p, blocks)
    x, v, u = _struct((tp, np_)), _struct((np_, kl)), _struct((kl, mp))
    return jax.eval_shape(
        lambda a, b, c: _lowrank_kernel(
            a, b, c, None, None, bt=blocks["bt"], bn=blocks["bn"],
            bm=blocks["bm"]),
        x, v, u)


def _lr_expected(p, blocks):
    tp, _, _, mp = _lr_dims(p, blocks)
    return _struct((tp, mp))


_LOWRANK = KernelContract(
    name="lowrank_matmul",
    align={"bt": _SUBLANE, "bn": _LANE, "bm": _LANE},
    probes=(
        {"t": 512, "n": 512, "k": 128, "m": 512},   # aligned
        {"t": 100, "n": 80, "k": 16, "m": 80},      # everything ragged
    ),
    candidates=lambda p: autotune.lowrank_candidates(
        p["t"], _rl(p["n"]), _rl(p["k"]), _rl(p["m"])),
    abstract_eval=_lr_abstract,
    expected=_lr_expected,
)


# ---------------------------------------------------------------------------
# flash_attention — GQA flash kernel over (B, H, L, D)


def _fa_dims(p, blocks):
    lqp = _ru(p["lq"], blocks["bq"])
    lkp = _ru(p["lk"], blocks["bk"])
    return lqp, lkp


def _fa_abstract(p, blocks):
    lqp, lkp = _fa_dims(p, blocks)
    q = _struct((p["b"], p["h"], lqp, p["d"]))
    k = _struct((p["b"], p["kv"], lkp, p["d"]))
    return jax.eval_shape(
        lambda a, b, c: _flash_kernel(
            a, b, c, causal=True, window=0,
            lk_valid=p["lk"] if lkp != p["lk"] else 0,
            bq=min(blocks["bq"], lqp), bk=min(blocks["bk"], lkp)),
        q, k, k)


def _fa_expected(p, blocks):
    lqp, _ = _fa_dims(p, blocks)
    return _struct((p["b"], p["h"], lqp, p["d"]))


_FLASH = KernelContract(
    name="flash_attention",
    align={"bq": _SUBLANE, "bk": _SUBLANE},
    probes=(
        {"b": 2, "h": 4, "kv": 2, "lq": 512, "lk": 512, "d": 128},
        {"b": 1, "h": 4, "kv": 4, "lq": 333, "lk": 257, "d": 128},
    ),
    candidates=lambda p: autotune.flash_candidates(p["lq"], p["lk"],
                                                   p["d"]),
    abstract_eval=_fa_abstract,
    expected=_fa_expected,
)


# ---------------------------------------------------------------------------
# flash_decode — one decode step against the factorized latent KV cache


def _fd_dims(p, blocks):
    # the wrapper clamps bk to L and pads L only off that grid; the ranks
    # go in as the cache holds them
    return _ru(p["l"], min(blocks["bk"], p["l"])), p["rk"], p["rv"]


def _fd_abstract(p, blocks):
    lp, rk, rv = _fd_dims(p, blocks)
    b, h, kv, d = p["b"], p["h"], p["kv"], p["d"]
    args = (
        _struct((b, h, d)),                       # q
        _struct((2, b, rk, lp)),                  # stacked latent K cache
        _struct((2, b, rv, lp)),                  # stacked latent V cache
        _struct((kv, rk, d)),                     # U_k
        _struct((kv, rv, d)),                     # U_v
        _struct((b,), jnp.int32),                 # lengths
        _struct((lp, max(d // 2, 1))),            # cos
        _struct((lp, max(d // 2, 1))),            # sin
        _struct((b, rk)),                         # this step's K latents
        _struct((b, rv)),                         # this step's V latents
        _struct((), jnp.int32),                   # layer
    )
    return jax.eval_shape(
        lambda *a: _decode_kernel(*a, use_rope=True,
                                  bk=min(blocks["bk"], lp)),
        *args)


def _fd_expected(p, blocks):
    lp, rk, rv = _fd_dims(p, blocks)
    b = p["b"]
    return (_struct((b, p["h"], p["d"])), _struct((2, b, rk, lp)),
            _struct((2, b, rv, lp)))


_DECODE = KernelContract(
    name="flash_decode",
    align={"bk": _LANE},
    probes=(
        {"b": 2, "h": 8, "kv": 2, "l": 1024, "d": 64, "rk": 128,
         "rv": 128},
        {"b": 1, "h": 4, "kv": 4, "l": 300, "d": 80, "rk": 24, "rv": 40},
    ),
    candidates=lambda p: autotune.flash_decode_candidates(
        p["l"], p["d"], _rl(p["rk"]), _rl(p["rv"]), p["kv"], p["h"]),
    abstract_eval=_fd_abstract,
    expected=_fd_expected,
)


# ---------------------------------------------------------------------------
# grouped_matmul — ragged expert GEMM over segment-sorted (M, d) rows


def _gm_dims(p, blocks):
    mp = _ru(p["m"], blocks["bm"])
    dl = _rl(p["d"])
    fp_ = _ru(_rl(p["f"]), blocks["bf"])
    return mp, dl, fp_


def _gm_abstract(p, blocks):
    mp, dl, fp_ = _gm_dims(p, blocks)
    x = _struct((mp, dl))
    w = _struct((p["e"], dl, fp_))
    gs = _struct((p["e"],), jnp.int32)
    return jax.eval_shape(
        lambda a, b, g: _grouped_kernel(
            a, b, g, bm=min(blocks["bm"], mp), bf=min(blocks["bf"], fp_)),
        x, w, gs)


def _gm_expected(p, blocks):
    mp, _, fp_ = _gm_dims(p, blocks)
    return _struct((mp, fp_))


_GROUPED = KernelContract(
    name="grouped_matmul",
    align={"bm": _SUBLANE, "bf": _LANE},
    probes=(
        {"m": 4096, "d": 2048, "f": 1408, "e": 64},   # deepseek-shaped
        {"m": 37, "d": 80, "f": 96, "e": 8},          # ragged everything:
        # rows far under a block, unaligned d/f — the drop-free smoke path
        {"m": 8, "d": 128, "f": 128, "e": 256},       # more experts than
        # rows: most groups empty, tile list dominated by sentinels
    ),
    candidates=lambda p: autotune.grouped_candidates(
        p["m"], _rl(p["d"]), p["f"], p["e"]),
    abstract_eval=_gm_abstract,
    expected=_gm_expected,
)


CONTRACTS: Dict[str, KernelContract] = {
    c.name: c for c in (_COV, _LOWRANK, _FLASH, _DECODE, _GROUPED)
}
