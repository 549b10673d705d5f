"""Serving: the compressed artifact behind the program's continuous-batching
engine (``ContinuousBatchingServer.run``).

The configuration's ``serve`` section gives the deployment: param dtype,
the AA-SVD ratio and rank multiple the factors are shaped by, the engine's
slots and ``max_len``, and ``requests_per_s``, the rate the deployment was
measured to complete, which sizes the window: ``ceil(requests_per_s ×
seconds)`` requests (at least one per slot).  The traffic file gives the
prompt and output length ranges and the loop:

* ``closed`` — one client per slot; a client sends its next request when
  its last one finishes (every request is queued at time 0, and the engine
  admits the next one into a slot as soon as it is free);
* ``open`` — requests arrive by a Poisson process at ``rate_per_s``.

Every seed gets the same set of lengths (stratified over the ranges, and
paired by a fixed permutation); the seed orders them and draws the token
ids.  Set-up makes the factors from the seed (no dense matrices, no
solve) and warms the engine on one request per prefill width the window
will use, which also compiles the decode step.

``correct``: a sample of the finished requests drawn from the seed, the
longest among them, runs through the plain float32 reference
(``bench/reference/decoder.py``) over its prompt and served tokens; the
number compared is ``logit_gap``, the widest gap by which a served token's
reference logit lies below the reference's best at that position.
"""

from __future__ import annotations

import gc
import math
import sys
import time
from typing import Dict, List

import numpy as np

from bench import harness, weights


def bucket(n: int, lo: int = 16) -> int:
    """The engine's prefill width for an n-token prompt: the next power of
    two at or above n (floor 16)."""
    w = lo
    while w < n:
        w *= 2
    return w


def lengths(lo: int, hi: int, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return lo + np.floor(q * (hi - lo + 1)).astype(np.int64)


def requests(traffic: Dict, seed: int, n: int, vocab: int) -> List[Dict]:
    """The window's requests: {rid, prompt, steps, arrival}."""
    plens = lengths(*traffic["prompt_tokens"], n)
    olens = lengths(*traffic["output_tokens"], n)[
        np.random.default_rng(0).permutation(n)]
    rng = np.random.default_rng([seed, 1])
    order = rng.permutation(n)
    if traffic["loop"] == "closed":
        arrivals = np.zeros(n)
    elif traffic["loop"] == "open":
        arrivals = np.cumsum(rng.exponential(1.0 / traffic["rate_per_s"], n))
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    out = []
    for i, j in enumerate(order):
        out.append({"rid": i, "steps": int(olens[j]),
                    "prompt": rng.integers(0, vocab, int(plens[j]),
                                           dtype=np.int32),
                    "arrival": float(arrivals[i])})
    return out


def window_requests(cell, seconds: float) -> int:
    sv = cell.config["serve"]
    return max(sv["slots"], math.ceil(sv["requests_per_s"] * seconds))


def sample(reqs: List[Dict], k: int, seed: int) -> List[int]:
    """Indices of ``k`` requests: the longest, and k − 1 drawn from the
    seed."""
    longest = max(range(len(reqs)), key=lambda i: len(reqs[i]["prompt"])
                  + reqs[i]["steps"])
    rest = [i for i in range(len(reqs)) if i != longest]
    rng = np.random.default_rng([seed, 2])
    picked = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(picked)]


def gaps(params, m: Dict, prompt: np.ndarray, served: np.ndarray,
         prec: str = "f32", pad_to: int = 0) -> np.ndarray:
    """Per served token: the reference's best logit minus its logit of the
    served token, at the position that predicted it.  With ``prec="low"``
    (the control) the token judged is the one the low-precision reference
    puts first there."""
    from bench.reference import decoder as D
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    p = len(prompt)
    ref = np.asarray(D.logits(params, m, seq, pad_to=pad_to),
                     np.float64)[p - 1:]
    if prec == "low":
        low = np.asarray(D.logits(params, m, seq, "low", pad_to))[p - 1:]
        served = np.argmax(low, axis=-1)
    return ref.max(-1) - ref[np.arange(len(served)), served]


def served_model(cell) -> Dict:
    return {**cell.config["model"],
            "param_dtype": cell.config["serve"]["param_dtype"]}


def make_params(cell, seed: int):
    sv = cell.config["serve"]
    cfg = weights.model_config(cell.config, param_dtype=sv["param_dtype"])
    return cfg, weights.make_params(cfg, seed, ratio=sv["ratio"],
                                    rank_multiple=sv["rank_multiple"])


def check_gap(cell, seed: int, reqs: List[Dict], results: Dict,
              prec: str = "f32") -> float:
    _, params = make_params(cell, seed)
    m = served_model(cell)
    widest = 0.0
    for i in sample(reqs, cell.traffic["check_requests"], seed):
        r = reqs[i]
        g = gaps(params, m, r["prompt"], results[r["rid"]]["tokens"], prec,
                 pad_to=cell.config["serve"]["max_len"])
        widest = max(widest, float(np.max(g)))
    return widest


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(cell, *, seed: int, seconds: float, trace: bool, t0: float):
    from repro.launch.serve import ContinuousBatchingServer, Request

    log = harness.CompileLog()
    sv = cell.config["serve"]
    m = served_model(cell)
    reqs = requests(cell.traffic, seed, window_requests(cell, seconds),
                    m["vocab_size"])
    with harness.span("bench.setup"):
        cfg, params = make_params(cell, seed)
        server = ContinuousBatchingServer(cfg, params, max_len=sv["max_len"],
                                          slots=sv["slots"])
        widths = sorted({bucket(len(r["prompt"])) for r in reqs})
        with harness.span("bench.warmup"):
            server.run([Request(rid=-1 - i, prompt=np.zeros(w, np.int32),
                                steps=2) for i, w in enumerate(widths)])
    window = [Request(rid=r["rid"], prompt=r["prompt"], steps=r["steps"],
                      arrival=r["arrival"]) for r in reqs]
    before = log.snapshot()
    setup_s = time.monotonic() - t0
    with harness.traced(trace) as tr:
        with harness.span(harness.WINDOW_SPAN):
            start = time.monotonic()
            with harness.span("bench.server_run"):
                results = server.run(window)
            window_s = time.monotonic() - start
    in_window = harness.CompileLog.delta(before, log.snapshot())
    peak = harness.peak_bytes()
    step_times = list(server.decode_step_times)
    del server, params
    gc.collect()

    done = [results[r["rid"]] for r in reqs if r["rid"] in results]
    n_tokens = sum(len(d["tokens"]) for d in done)
    ttft = [d["first_token"] - d["admitted"] for d in done]
    tpot = [(d["done"] - d["first_token"]) / (len(d["tokens"]) - 1)
            for d in done if len(d["tokens"]) > 1]
    with harness.span("bench.reference"):
        t_ref = time.monotonic()
        gap = check_gap(cell, seed, reqs, results)
        ref_s = time.monotonic() - t_ref
    print(f"[serve] requests={len(reqs)} done={len(done)} tokens={n_tokens} "
          f"steps={len(step_times)} window_s={window_s!r} "
          f"setup_s={setup_s!r} reference_s={ref_s!r}", flush=True)
    print(f"[serve] in_window={in_window}", file=sys.stderr)
    return harness.Run(
        cell=cell, model=m,
        end_to_end={"decode_tokens_per_s": n_tokens / window_s,
                    "ttft_p95_ms": 1e3 * percentile(ttft, 95),
                    "tpot_p95_ms": 1e3 * percentile(tpot, 95),
                    "setup_s": setup_s},
        counters={"memory_peak_bytes": peak, "requests": reqs,
                  "results": results, "decode_step_times": step_times,
                  "in_window": in_window},
        checks=harness.checks(cell, {"logit_gap": gap}),
        attempted=len(reqs), failed=len(reqs) - len(done),
        window_s=window_s, peaks=harness.device_peaks(), trace=tr.summary)
