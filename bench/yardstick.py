"""Operations and bytes the benchmark's work needs, computed from shapes,
and the chip's peaks.

These are the algorithm's own counts, not what an implementation happens
to do: a kernel that reads more than it needs, or computes masked-out
blocks, shows as a lower share of its roofline.  Matmul FLOPs count
2 per multiply-add.  The Jacobi eigensolves of the compression solve are
counted at a stated nominal ``EIGH_SWEEPS`` sweeps of ``9 n^3`` FLOPs
(two-sided rotations of the matrix plus the accumulated eigenvectors).
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Sequence, Tuple

from bench.reference.aasvd import GROUPS, rank_for_ratio

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
EIGH_SWEEPS = 10
BF16 = 2
F32 = 4


def peaks(device_kind: str) -> Dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; known: {sorted(table)}")
    return table[device_kind]


def linears(m: Dict) -> List[Tuple[str, str, int, int]]:
    """(group, name, n_in, n_out) of one decoder layer's linears."""
    d, h, kv, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    dims = {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d), "gate": (d, f), "up": (d, f), "down": (f, d)}
    return [(g, name, *dims[name]) for g, members in GROUPS
            for _, name in members]


def ranks(m: Dict, ratio: float, multiple: int) -> Dict[str, int]:
    return {name: rank_for_ratio(n, o, ratio, multiple)
            for _, name, n, o in linears(m)}


def layer_params(m: Dict, ratio: float = 1.0, multiple: int = 8,
                 factorized: Sequence[str] = ()) -> int:
    """Matmul params of one layer; linears named in ``factorized`` (all of
    them when ``ratio`` < 1 and ``factorized`` is empty) count k (n + m)."""
    rk = ranks(m, ratio, multiple) if ratio < 1.0 else {}
    fac = set(factorized) if factorized else set(rk)
    return sum(rk[name] * (n + o) if name in fac else n * o
               for _, name, n, o in linears(m))


def attention_flops(m: Dict, context: float) -> float:
    """QKᵀ and PV of one token against ``context`` keys, one layer."""
    return 4.0 * m["num_heads"] * m["head_dim"] * context


def causal_context(seq: int) -> float:
    """Mean keys a query sees in causal attention over ``seq`` tokens."""
    return (seq + 1) / 2.0


# ---------------------------------------------------------------------------
# compression


def cov_flops_bytes(m: Dict, tokens: int) -> Tuple[float, float]:
    """One layer's covariance products: Xᵀ X, Xᵀ X', X'ᵀ X' for each
    group, over ``tokens`` rows of bfloat16 taps -> (FLOPs, bytes: both
    inputs read once, three float32 products written)."""
    flops = byts = 0.0
    for group, members in GROUPS:
        n = {name: nin for _, name, nin, _ in linears(m)}[members[0][1]]
        flops += 3 * 2.0 * tokens * n * n
        byts += 2 * tokens * n * BF16 + 3 * n * n * F32
    return flops, byts


def solve_flops(m: Dict, ratio: float, multiple: int) -> float:
    """One layer's anchored solves: whitening eigensolve per group, then
    per linear Wᵀ C L⁻ᵀ, the Gram matrix, its eigensolve and the factor
    products."""
    rk = ranks(m, ratio, multiple)
    flops = 0.0
    seen = set()
    for group, name, n, o in linears(m):
        if group not in seen:
            seen.add(group)
            flops += EIGH_SWEEPS * 9.0 * n ** 3 + 2.0 * n ** 3
        small, big = min(n, o), max(n, o)
        flops += 2.0 * n * n * n + 2.0 * o * n * n          # Wᵀ (C L⁻ᵀ)
        flops += 2.0 * small * small * big                 # Gram
        flops += EIGH_SWEEPS * 9.0 * small ** 3            # its eigh
        flops += 2.0 * small * big * small                 # other side
        flops += 2.0 * n * n * rk[name]                    # v = L⁻ᵀ B
    return flops


def traced_layers(run, program: str = "solve_anchored") -> float:
    """Layers of a traced compression window that the trace holds, from
    the executions of ``program`` in it: the job runs one
    ``solve_anchored`` per linear and one ``update_covs`` per group and
    calibration microbatch."""
    tr = run.cell.traffic
    per_layer = {"solve_anchored": len(linears(run.model)),
                 "update_covs": len(GROUPS) * (tr["calib_sequences"]
                                               // tr["compress"]["microbatch"])}
    return run.trace.module_count([program]) / per_layer[program]


def compress_layer_flops(m: Dict, job: Dict, seq: int, sequences: int
                         ) -> float:
    """FLOPs one layer of the sequential AA-SVD job needs: every tapped
    forward (original and shifted stream, once per group), the anchors,
    the refinement when the job refines (forward + backward = 3 forwards
    per step, plus the pre/post evaluations), the stream propagation, the covariance
    products and the solves."""
    tokens = seq * sequences
    ctx = causal_context(seq)
    ratio, mult = job["ratio"], job["rank_multiple"]
    att = attention_flops(m, ctx)
    dense = 2.0 * layer_params(m) + att
    comp = 2.0 * layer_params(m, ratio, mult) + att
    fwd = 0.0
    solved: List[str] = []
    for group, members in GROUPS:
        part = 2.0 * layer_params(m, ratio, mult, factorized=solved) + att \
            if solved else dense
        fwd += dense + part
        solved += [name for _, name in members]
    flops = tokens * fwd                                   # calibration
    flops += tokens * dense                                # anchors
    passes = 3 * job["refine_epochs"] + 2 if job.get("refine", True) else 0
    flops += tokens * comp * (passes + 1)
    flops += cov_flops_bytes(m, tokens)[0]
    flops += solve_flops(m, ratio, mult)
    return flops


# ---------------------------------------------------------------------------
# serving


def cache_bytes_per_token(m: Dict, ratio: float, multiple: int,
                          latent: bool) -> float:
    """Cache bytes one token adds to one layer (bfloat16)."""
    if latent:
        rk = ranks(m, ratio, multiple)
        return (rk["wk"] + rk["wv"]) * BF16
    return 2 * m["num_kv_heads"] * m["head_dim"] * BF16


def decode_weight_bytes(m: Dict, ratio: float, multiple: int) -> float:
    """Weights one decode step reads: every layer's factors and the head
    (bfloat16)."""
    return (m["num_layers"] * layer_params(m, ratio, multiple)
            + m["d_model"] * m["vocab_size"]) * BF16


def decode_token_flops(m: Dict, ratio: float, multiple: int,
                       context: float) -> float:
    """FLOPs of one decoded token: 2 × matmul params (layers and head)
    plus attention at ``context`` keys in every layer."""
    return (2.0 * (m["num_layers"] * layer_params(m, ratio, multiple)
                   + m["d_model"] * m["vocab_size"])
            + m["num_layers"] * attention_flops(m, context))


def decode_contexts(requests) -> Tuple[float, int]:
    """(keys attended summed over every decoded token, decoded tokens) of
    the window's requests: a request of p prompt tokens and s served
    tokens decodes s − 1 of them (the first comes from prefill), at p + 1
    ... p + s − 1 keys."""
    keys = tokens = 0
    for r in requests:
        p, n = len(r["prompt"]), r["steps"] - 1
        keys += n * (p + 1) + n * (n - 1) // 2
        tokens += n
    return float(keys), tokens


def flash_decode_flops_bytes(m: Dict, ratio: float, multiple: int,
                             keys: float, slot_steps: int, calls: int
                             ) -> Tuple[float, float]:
    """The latent-cache decode kernel's work: ``keys`` filled positions
    attended in all, over ``slot_steps`` (slot, step) pairs, in ``calls``
    kernel calls (one per layer and step).  Per key: the key up-projection (r_k → KV
    heads × D), scores, and the value accumulation in latent space; per
    slot-step the U_v epilogue.  Bytes: the filled latents, U_k and U_v
    once a call, q and the output."""
    rk = ranks(m, ratio, multiple)
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    r_k, r_v = rk["wk"], rk["wv"]
    flops = (keys * (2.0 * r_k * kv * hd + 2.0 * h * hd + 2.0 * h * r_v)
             + slot_steps * 2.0 * h * r_v * hd)
    byts = (keys * (r_k + r_v) * BF16 + calls * (r_k + r_v) * kv * hd * BF16
            + slot_steps * 2 * h * hd * BF16)
    return flops, byts
