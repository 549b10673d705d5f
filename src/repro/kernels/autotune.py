"""Block-shape autotuner: measure-and-cache over a per-kernel lattice.

Every Pallas kernel in this package takes its block shapes as static
arguments; until now the dispatch wrappers in ``kernels.ops`` hand-picked
them.  This module replaces those constants with a small roller-style
policy (in the spirit of AttentionEngine's tensorcore roller): each kernel
exposes a *lattice* of candidate block shapes, candidates are filtered by

* **divisibility / clamping** — a block may never exceed the lane-aligned
  problem dimension it tiles (the wrappers pad dims up to the chosen block,
  and zero-row/column padding is exact, so padding *waste* is bounded
  instead: candidates that more than double the padded work are dropped,
  unless nothing else survives), and
* **VMEM fit** — the pipelined working set (double-buffered input/output
  blocks + scratch) must fit the per-core VMEM budget
  (``REPRO_AUTOTUNE_VMEM_BYTES``, default 12 MiB of the ~16 MiB core),

then either *measured* — each surviving candidate's compiled kernel is
timed (median of ``iters`` calls after a warmup) and the fastest wins — or
picked by a *deterministic heuristic*: the filtered lattice is
preference-sorted by (padding waste, distance from the hand-tuned anchor
shape), and the first entry wins.  Measurement is the default on a real
TPU backend; CPU/GPU runs (including ``interpret=True`` correctness runs)
take the heuristic, which reproduces the previous hand-picked constants on
aligned shapes — unless measurement is forced (``mode="measure"``), which
the wall-clock benchmark uses to time interpret-mode kernels on CPU.

Measured picks persist to a keyed on-disk JSON cache so every process (and
every trace) after the first reuses the same shapes:

    key = <kernel>|v<CACHE_VERSION>|<backend>:<device_kind>[:interp]|<sig>

where ``sig`` encodes the lane-padded problem dims, dtype and kernel flags.
The cache lives at ``$REPRO_AUTOTUNE_CACHE`` (default
``~/.cache/aa-svd/autotune.json``); delete the file, call
``clear_disk_cache()``, or bump ``CACHE_VERSION`` (done whenever a kernel's
grid/spec layout changes) to refresh.  Heuristic picks are pure functions
of the lattice and are not persisted.  See ``kernels/README.md``.
"""

from __future__ import annotations

import json
import logging
import math
import os
import tempfile
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CACHE_VERSION = 2

log = logging.getLogger(__name__)

# hand-tuned anchors: the block shapes ops.py shipped with before the
# autotuner existed — the heuristic's preferred point on each lattice
_ANCHORS = {
    "cov_accum": {"bt": 512, "bi": 256},
    "lowrank_matmul": {"bt": 256, "bn": 512, "bm": 256},
    "flash_attention": {"bq": 256, "bk": 256},
    "flash_decode": {"bk": 256},
    "grouped_matmul": {"bm": 128, "bf": 256},
}

# candidate lattices (per block dim).  Small on purpose: measurement cost
# is one compile + a few timed calls per candidate, and the preference
# sort measures only the top REPRO_AUTOTUNE_MAX_CANDIDATES survivors.
_LATTICES = {
    "cov_accum": {"bt": (128, 256, 512, 1024), "bi": (128, 256, 512)},
    "lowrank_matmul": {"bt": (128, 256, 512), "bn": (128, 256, 512),
                       "bm": (128, 256, 512)},
    "flash_attention": {"bq": (128, 256, 512), "bk": (128, 256, 512)},
    "flash_decode": {"bk": (128, 256, 512, 1024)},
    # row blocks small: the ragged tiling revisits a (bm, bf) output block
    # once per expert straddling it, so oversized bm multiplies revisits
    "grouped_matmul": {"bm": (128, 256, 512), "bf": (128, 256, 512)},
}

_LANE = 128          # last-dim tile multiple (fp32 8×128, bf16 16×128)
_MAX_WASTE = 1.0     # candidates may at most double the padded work


class TuneResult(NamedTuple):
    """One autotune decision: the chosen blocks, where they came from
    (``heuristic`` | ``measured`` | ``cache``), and the measured median
    µs/call when a measurement happened (None for heuristic picks)."""

    blocks: Dict[str, int]
    source: str
    us: Optional[float]


class Candidate(NamedTuple):
    blocks: Dict[str, int]
    vmem_bytes: int
    waste: float


# ---------------------------------------------------------------------------
# knobs (env-overridable so tests and the benchmark can pin them)


def _vmem_budget() -> int:
    return int(os.environ.get("REPRO_AUTOTUNE_VMEM_BYTES", 12 * 2 ** 20))


def _max_measured() -> int:
    return int(os.environ.get("REPRO_AUTOTUNE_MAX_CANDIDATES", 8))


def _cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "aa-svd",
                     "autotune.json"))


def _mode(mode: str) -> str:
    """Resolve "auto": measure on a real TPU backend, heuristic elsewhere
    (interpret-mode timings are not a Mosaic proxy).  ``REPRO_AUTOTUNE``
    overrides everything — including explicit call-site modes — so a run
    can be pinned from the environment."""
    mode = os.environ.get("REPRO_AUTOTUNE", mode)
    if mode != "auto":
        return mode
    return "measure" if jax.default_backend() == "tpu" else "heuristic"


# ---------------------------------------------------------------------------
# cache


_MEM: Dict[str, TuneResult] = {}
_DISK: Optional[Dict[str, dict]] = None


def reset(disk: bool = False) -> None:
    """Drop the in-memory caches (tests flip env knobs between calls);
    ``disk=True`` also deletes the on-disk cache file."""
    global _DISK
    _MEM.clear()
    _DISK = None
    if disk:
        clear_disk_cache()


def clear_disk_cache() -> None:
    global _DISK
    _DISK = None
    try:
        os.remove(_cache_path())
    except OSError:
        pass


def _disk() -> Dict[str, dict]:
    global _DISK
    if _DISK is None:
        try:
            with open(_cache_path()) as f:
                _DISK = json.load(f)
        except (OSError, ValueError):
            _DISK = {}
    return _DISK


def _disk_put(key: str, entry: dict) -> None:
    """Merge one measured entry into the on-disk cache (atomic replace —
    concurrent processes lose at worst a benign re-measurement)."""
    path = _cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    merged = dict(_disk())
    merged[key] = entry
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               prefix=".autotune-")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(merged, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        try:
            os.remove(tmp)
        except OSError:
            pass
    global _DISK
    _DISK = merged


def _device_sig(interpret: bool) -> str:
    kind = jax.devices()[0].device_kind.replace(" ", "_")
    sig = f"{jax.default_backend()}:{kind}"
    return sig + ":interp" if interpret else sig


def _key(kernel: str, sig: str, interpret: bool) -> str:
    return f"{kernel}|v{CACHE_VERSION}|{_device_sig(interpret)}|{sig}"


# ---------------------------------------------------------------------------
# lattice construction


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pick_valid(dim: int, cands: Sequence[int], lane: int) -> List[int]:
    """Blocks for one dimension: never larger than the lane-padded dim
    (the wrapper would just clamp them), never more than doubling the
    padded work — with the smallest-waste candidate as a floor so tiny
    dims still yield exactly one block."""
    padded_dim = _round_up(dim, lane)
    ok = [b for b in cands
          if b <= padded_dim and (_round_up(dim, b) / dim - 1) <= _MAX_WASTE]
    if not ok:
        ok = [min(cands, key=lambda b: (_round_up(dim, b), b))]
    return ok


def _bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _prefer(kernel: str, cand: Candidate) -> Tuple:
    """Deterministic preference: least padding waste first, then closest
    to the hand-tuned anchor (log-distance per block dim), then the blocks
    themselves as an unambiguous tiebreak."""
    anchor = _ANCHORS[kernel]
    dist = sum(abs(math.log2(cand.blocks[k]) - math.log2(anchor[k]))
               for k in anchor)
    return (round(cand.waste, 6), dist,
            tuple(cand.blocks[k] for k in sorted(cand.blocks)))


def cov_candidates(t: int, n: int, dtype=jnp.float32) -> List[Candidate]:
    """(bt, bi) lattice for ``cov_accum`` on lane-padded (t, n) token rows.
    VMEM working set: 4 double-buffered (bt, bi) input tiles + 3
    double-buffered (bi, bi) fp32 output tiles."""
    out = []
    eb = _bytes(dtype)
    for bt in _pick_valid(t, _LATTICES["cov_accum"]["bt"], 8):
        for bi in _pick_valid(n, _LATTICES["cov_accum"]["bi"], _LANE):
            vmem = 2 * (4 * bt * bi * eb + 3 * bi * bi * 4)
            waste = (_round_up(t, bt) * _round_up(n, bi)) / (t * n) - 1
            if vmem <= _vmem_budget():
                out.append(Candidate({"bt": bt, "bi": bi}, vmem, waste))
    if not out:  # degenerate budget: keep the smallest-footprint candidate
        bt = min(_LATTICES["cov_accum"]["bt"])
        bi = min(_LATTICES["cov_accum"]["bi"])
        out = [Candidate({"bt": bt, "bi": bi},
                         2 * (4 * bt * bi * eb + 3 * bi * bi * 4), 0.0)]
    return sorted(out, key=lambda c: _prefer("cov_accum", c))


def lowrank_candidates(t: int, n: int, k: int, m: int, dtype=jnp.float32,
                       has_bias: bool = False,
                       has_residual: bool = False) -> List[Candidate]:
    """(bt, bn, bm) lattice for the phase-fused factorized GEMM.  VMEM:
    double-buffered x (bt, bn), V (bn, k), U (k, bm), y (bt, bm) (+ bias /
    residual epilogue tiles) + the fp32 (bt, k) intermediate scratch."""
    out = []
    eb = _bytes(dtype)
    lat = _LATTICES["lowrank_matmul"]
    for bt in _pick_valid(t, lat["bt"], 8):
        for bn in _pick_valid(n, lat["bn"], _LANE):
            for bm in _pick_valid(m, lat["bm"], _LANE):
                tiles = (bt * bn + bn * k + k * bm + bt * bm
                         + (bm if has_bias else 0)
                         + (bt * bm if has_residual else 0))
                vmem = 2 * tiles * eb + bt * k * 4
                waste = (_round_up(t, bt) * _round_up(n, bn)
                         * _round_up(m, bm)) / (t * n * m) - 1
                if vmem <= _vmem_budget():
                    out.append(Candidate(
                        {"bt": bt, "bn": bn, "bm": bm}, vmem, waste))
    if not out:
        bt, bn, bm = (min(lat["bt"]), min(lat["bn"]), min(lat["bm"]))
        out = [Candidate({"bt": bt, "bn": bn, "bm": bm},
                         2 * (bt * bn + bn * k + k * bm + bt * bm) * eb
                         + bt * k * 4, 0.0)]
    return sorted(out, key=lambda c: _prefer("lowrank_matmul", c))


def grouped_candidates(m: int, d: int, f: int, e: int,
                       dtype=jnp.float32) -> List[Candidate]:
    """(bm, bf) lattice for the grouped expert GEMM on (m, d) sorted rows ×
    (e, d, f) banks.  VMEM: double-buffered x (bm, d) + W (d, bf) tiles
    plus the fp32 (bm, bf) resident output block; the contraction dim d is
    not tiled (it rides whole in each tile), so big-d problems thin the
    lattice toward small blocks."""
    out = []
    eb = _bytes(dtype)
    lat = _LATTICES["grouped_matmul"]
    for bm in _pick_valid(m, lat["bm"], 8):
        for bf in _pick_valid(f, lat["bf"], _LANE):
            vmem = 2 * ((bm * d + d * bf) * eb + bm * bf * 4)
            waste = (_round_up(m, bm) * _round_up(f, bf)) / (m * f) - 1
            if vmem <= _vmem_budget():
                out.append(Candidate({"bm": bm, "bf": bf}, vmem, waste))
    if not out:
        bm, bf = min(lat["bm"]), min(lat["bf"])
        out = [Candidate({"bm": bm, "bf": bf},
                         2 * ((bm * d + d * bf) * eb + bm * bf * 4), 0.0)]
    return sorted(out, key=lambda c: _prefer("grouped_matmul", c))


def flash_candidates(lq: int, lk: int, d: int,
                     dtype=jnp.float32) -> List[Candidate]:
    """(bq, bk) lattice for flash attention.  VMEM: double-buffered q/o
    (bq, d) + k/v (bk, d) tiles + fp32 (bq, d) accumulator and (bq, 1)
    max/denom scratch."""
    out = []
    eb = _bytes(dtype)
    lat = _LATTICES["flash_attention"]
    for bq in _pick_valid(lq, lat["bq"], 8):
        for bk in _pick_valid(lk, lat["bk"], 8):
            vmem = (2 * (2 * bq * d + 2 * bk * d) * eb
                    + (bq * d + 2 * bq) * 4)
            waste = (_round_up(lq, bq) * _round_up(lk, bk)) / (lq * lk) - 1
            if vmem <= _vmem_budget():
                out.append(Candidate({"bq": bq, "bk": bk}, vmem, waste))
    if not out:
        bq, bk = min(lat["bq"]), min(lat["bk"])
        out = [Candidate({"bq": bq, "bk": bk},
                         2 * (2 * bq * d + 2 * bk * d) * eb
                         + (bq * d + 2 * bq) * 4, 0.0)]
    return sorted(out, key=lambda c: _prefer("flash_attention", c))


def flash_decode_candidates(l: int, d: int, rk: int, rv: int, kv: int,
                            h: int,
                            dtype=jnp.float32) -> List[Candidate]:
    """(bk,) lattice for the factorized flash-decode kernel.  bk is the
    lane dim of the rank-major latent blocks.  VMEM: double-buffered
    latent (r_k, bk) + (r_v, bk) tiles and (D/2, bk) rope tables, the
    resident q/o (H, D) + U factors (KV, r, D), and the fp32 (H, r_v)
    accumulator + (H, 1) stats scratch."""
    out = []
    eb = _bytes(dtype)
    lat = _LATTICES["flash_decode"]
    resident = (2 * h * d + kv * (rk + rv) * d) * eb
    for bk in _pick_valid(l, lat["bk"], _LANE):
        vmem = (2 * (bk * rk + bk * rv + bk * d) * eb
                + resident + (h * rv + 2 * h) * 4)
        waste = _round_up(l, bk) / l - 1
        if vmem <= _vmem_budget():
            out.append(Candidate({"bk": bk}, vmem, waste))
    if not out:
        bk = min(lat["bk"])
        out = [Candidate({"bk": bk},
                         2 * (bk * rk + bk * rv + bk * d) * eb + resident,
                         0.0)]
    return sorted(out, key=lambda c: _prefer("flash_decode", c))


# ---------------------------------------------------------------------------
# measurement


def _time_call(fn: Callable, args: tuple, warmup: int = 1,
               iters: int = 3) -> float:
    """Median µs per call of ``fn`` compiled ahead of time for ``args``.

    The explicit lower/compile keeps the timed call off any trace that is
    active around the tuner, so the time is the compiled kernel's."""
    compiled = jax.jit(fn).lower(*args).compile()
    for _ in range(warmup):
        jax.block_until_ready(compiled(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def _measure_best(cands: Sequence[Candidate],
                  thunk: Callable[[Candidate], Tuple[Callable, tuple]],
                  ) -> Tuple[Candidate, float]:
    """Time the top preference-ranked candidates (compiled-call medians)
    and return the fastest.  ``thunk(cand) -> (fn, args)`` builds the
    kernel call for one candidate.

    The tuner is consulted while an outer function is being traced, where
    every array op would be staged into that trace: the probe arrays are
    built concretely (``ensure_compile_time_eval``) and ``_time_call``
    runs a separately compiled kernel, so the timing measures the kernel
    and not tracing.  A candidate whose compile or run fails is logged
    with its blocks and skipped; if every candidate fails, the error is
    raised — a kernel the compiler rejects never hides behind a default
    block choice."""
    best: Optional[Tuple[Candidate, float]] = None
    failures = []
    for cand in list(cands)[:_max_measured()]:
        try:
            with jax.ensure_compile_time_eval():
                fn, args = thunk(cand)
            us = _time_call(fn, args)
        except Exception as e:  # noqa: BLE001; repro-check: allow[bare-except] — logged with its blocks; raised below when no candidate survives
            log.warning("autotune: candidate %s failed: %s: %s",
                        cand.blocks, type(e).__name__, e)
            failures.append((cand.blocks, e))
            continue
        if best is None or us < best[1]:
            best = (cand, us)
    if best is None:
        blocks = [b for b, _ in failures]
        raise RuntimeError(
            f"autotune: every measured candidate failed {blocks}; "
            f"last error: {failures[-1][1]}") from failures[-1][1]
    return best


def _tune(kernel: str, sig: str, cands: Sequence[Candidate],
          thunk: Callable, mode: str, interpret: bool) -> TuneResult:
    key = _key(kernel, sig, interpret)
    hit = _MEM.get(key + f"|{_mode(mode)}")
    if hit is not None:
        return hit
    resolved = _mode(mode)
    if resolved == "measure":
        entry = _disk().get(key)
        if entry is not None:
            res = TuneResult(dict(entry["blocks"]), "cache",
                             entry.get("us"))
        else:
            cand, us = _measure_best(cands, thunk)
            res = TuneResult(dict(cand.blocks), "measured", us)
            _disk_put(key, {"blocks": res.blocks, "us": res.us})
    else:  # heuristic (and "off", which is the anchor-flavoured heuristic)
        res = TuneResult(dict(cands[0].blocks), "heuristic", None)
    _MEM[key + f"|{resolved}"] = res
    return res


# ---------------------------------------------------------------------------
# public per-kernel entry points (called by kernels.ops at trace time —
# all-static arguments, so lookups are pure Python)


def cov_blocks(t: int, n: int, *, dtype=jnp.float32, mode: str = "auto",
               interpret: bool = False) -> TuneResult:
    """Blocks for ``cov_accum`` on (t, n) token rows (n lane-padded by the
    caller; the caller then pads t and n up to the returned blocks)."""
    cands = cov_candidates(t, n, dtype)
    sig = f"t{t}-n{n}-{jnp.dtype(dtype).name}"

    def thunk(c: Candidate):
        from repro.kernels.cov_accum import cov_accum as kern
        tp = _round_up(t, c.blocks["bt"])
        np_ = _round_up(n, c.blocks["bi"])
        x = jnp.ones((tp, np_), dtype)
        return (lambda a, b: kern(a, b, bi=c.blocks["bi"],
                                  bt=c.blocks["bt"], interpret=interpret),
                (x, x))

    return _tune("cov_accum", sig, cands, thunk, mode, interpret)


def lowrank_blocks(t: int, n: int, k: int, m: int, *, dtype=jnp.float32,
                   has_bias: bool = False, has_residual: bool = False,
                   mode: str = "auto",
                   interpret: bool = False) -> TuneResult:
    """Blocks for the phase-fused (x@V)@U GEMM (n/k/m lane-padded by the
    caller; t and the block-tiled dims are padded up to the pick)."""
    cands = lowrank_candidates(t, n, k, m, dtype, has_bias, has_residual)
    sig = (f"t{t}-n{n}-k{k}-m{m}-{jnp.dtype(dtype).name}"
           f"-b{int(has_bias)}r{int(has_residual)}")

    def thunk(c: Candidate):
        from repro.kernels.lowrank_matmul import lowrank_matmul as kern
        bt, bn, bm = c.blocks["bt"], c.blocks["bn"], c.blocks["bm"]
        tp, np_, mp = _round_up(t, bt), _round_up(n, bn), _round_up(m, bm)
        x = jnp.ones((tp, np_), dtype)
        v = jnp.ones((np_, k), dtype)
        u = jnp.ones((k, mp), dtype)
        bias = jnp.zeros((1, mp), dtype) if has_bias else None
        res = jnp.zeros((tp, mp), dtype) if has_residual else None
        return (lambda *a: kern(*a, bt=bt, bn=bn, bm=bm,
                                interpret=interpret),
                (x, v, u, bias, res))

    return _tune("lowrank_matmul", sig, cands, thunk, mode, interpret)


def grouped_blocks(m: int, d: int, f: int, e: int, *, dtype=jnp.float32,
                   mode: str = "auto",
                   interpret: bool = False) -> TuneResult:
    """Blocks for the grouped expert GEMM (d lane-padded by the caller;
    rows and f are padded up to the pick).  The probe routes rows evenly
    across the e groups — the balanced case every MoE load-balance loss
    pushes toward."""
    cands = grouped_candidates(m, d, f, e, dtype)
    sig = f"m{m}-d{d}-f{f}-e{e}-{jnp.dtype(dtype).name}"

    def thunk(c: Candidate):
        from repro.kernels.grouped_matmul import grouped_matmul as kern
        bm, bf = c.blocks["bm"], c.blocks["bf"]
        mp, fp_ = _round_up(m, bm), _round_up(f, bf)
        x = jnp.ones((mp, d), dtype)
        w = jnp.ones((e, d, fp_), dtype)
        gs = jnp.full((e,), m // e, jnp.int32)
        gs = gs.at[0].add(m - int(m // e) * e)
        return (lambda a, b, g: kern(a, b, g, bm=min(bm, mp),
                                     bf=min(bf, fp_), interpret=interpret),
                (x, w, gs))

    return _tune("grouped_matmul", sig, cands, thunk, mode, interpret)


def flash_blocks(b: int, h: int, kv: int, lq: int, lk: int, d: int, *,
                 dtype=jnp.float32, causal: bool = True, window: int = 0,
                 mode: str = "auto",
                 interpret: bool = False) -> TuneResult:
    """Blocks for flash attention; lq/lk are the UNPADDED sequence lengths
    (the caller pads each up to the returned block)."""
    cands = flash_candidates(lq, lk, d, dtype)
    sig = (f"b{b}-h{h}-kv{kv}-lq{lq}-lk{lk}-d{d}"
           f"-{jnp.dtype(dtype).name}-c{int(causal)}w{window}")

    def thunk(c: Candidate):
        from repro.kernels.flash_attention import flash_attention as kern
        bq, bk = c.blocks["bq"], c.blocks["bk"]
        q = jnp.ones((b, h, _round_up(lq, bq), d), dtype)
        kx = jnp.ones((b, kv, _round_up(lk, bk), d), dtype)
        return (lambda qq, kk, vv: kern(qq, kk, vv, causal=causal,
                                        window=window, bq=bq, bk=bk,
                                        interpret=interpret),
                (q, kx, kx))

    return _tune("flash_attention", sig, cands, thunk, mode, interpret)


def flash_decode_blocks(b: int, h: int, kv: int, l: int, d: int,
                        rk: int, rv: int, *, dtype=jnp.float32,
                        use_rope: bool = True, mode: str = "auto",
                        interpret: bool = False) -> TuneResult:
    """Blocks for the factorized flash-decode kernel; ``l`` is the UNPADDED
    cache length (the caller pads it up to the returned block) and rk/rv
    the latent ranks as the cache holds them (the VMEM model counts them
    lane-padded, as Mosaic lays them out)."""
    cands = flash_decode_candidates(l, d, _round_up(rk, 128),
                                    _round_up(rv, 128), kv, h, dtype)
    sig = (f"b{b}-h{h}-kv{kv}-l{l}-d{d}-rk{rk}-rv{rv}"
           f"-{jnp.dtype(dtype).name}-r{int(use_rope)}")

    def thunk(c: Candidate):
        from repro.kernels.flash_decode import flash_decode as kern
        bk = c.blocks["bk"]
        lp = _round_up(l, bk)
        q = jnp.ones((b, h, d), dtype)
        lkx = jnp.ones((b, rk, lp), dtype)
        lvx = jnp.ones((b, rv, lp), dtype)
        uk = jnp.ones((kv, rk, d), dtype)
        uv = jnp.ones((kv, rv, d), dtype)
        lengths = jnp.full((b,), l, jnp.int32)
        cs = jnp.ones((lp, max(d // 2, 1)), dtype)
        kn, vn = jnp.ones((b, rk), dtype), jnp.ones((b, rv), dtype)
        return (lambda *a: kern(*a, use_rope=use_rope, bk=bk,
                                interpret=interpret),
                (q, lkx, lvx, uk, uv, lengths, cs, cs, kn, vn))

    return _tune("flash_decode", sig, cands, thunk, mode, interpret)
