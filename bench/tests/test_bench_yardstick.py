"""FLOP and byte counts against hand counts at smoke shapes, and the
peaks table."""

import pytest

from bench import yardstick as Y

M = dict(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
         vocab_size=256, num_layers=2)


def test_ranks_and_params():
    # k = ceil8(floor(0.6 mn/(m+n))), capped at mn/(m+n)
    assert Y.ranks(M, 0.6, 8) == {"wq": 24, "wk": 16, "wv": 16, "wo": 24,
                                  "gate": 32, "up": 32, "down": 32}
    assert Y.layer_params(M) == 4096 + 2048 + 2048 + 4096 + 3 * 8192
    assert Y.layer_params(M, 0.6, 8) == (24 * 128 + 2 * 16 * 96 + 24 * 128
                                         + 3 * 32 * 192)
    # only q/k/v solved so far
    assert Y.layer_params(M, 0.6, 8, factorized=["wq", "wk", "wv"]) == (
        24 * 128 + 2 * 16 * 96 + 4096 + 3 * 8192)


def test_published_ranks():
    granite = dict(d_model=4096, num_heads=32, num_kv_heads=8, head_dim=128,
                   d_ff=12800)
    assert Y.ranks(granite, 0.6, 8) == {"wq": 1232, "wk": 496, "wv": 496,
                                        "wo": 1232, "gate": 1864,
                                        "up": 1864, "down": 1864}


def test_cov_counts():
    flops, byts = Y.cov_flops_bytes(M, 100)
    widths = [64, 64, 64, 128]                  # qkv, o, gate/up, down
    assert flops == sum(6 * 100 * n * n for n in widths)
    assert byts == sum(2 * 100 * n * 2 + 3 * n * n * 4 for n in widths)


def test_decode_counts():
    reqs = [{"prompt": [0] * 10, "steps": 4}, {"prompt": [0] * 5,
                                               "steps": 1}]
    keys, tokens = Y.decode_contexts(reqs)
    assert (keys, tokens) == (11 + 12 + 13, 3)
    assert Y.decode_token_flops(M, 0.6, 8, 10.0) == (
        2 * (2 * Y.layer_params(M, 0.6, 8) + 64 * 256) + 2 * 4 * 4 * 16 * 10)
    assert Y.cache_bytes_per_token(M, 0.6, 8, latent=True) == (16 + 16) * 2
    assert Y.cache_bytes_per_token(M, 0.6, 8, latent=False) == 2 * 2 * 16 * 2
    flops, byts = Y.flash_decode_flops_bytes(M, 0.6, 8, keys=100,
                                             slot_steps=3, calls=2)
    per_key = 2 * 16 * 2 * 16 + 2 * 4 * 16 + 2 * 4 * 16
    assert flops == 100 * per_key + 3 * 2 * 4 * 16 * 16
    assert byts == 100 * 32 * 2 + 2 * 32 * 2 * 16 * 2 + 3 * 2 * 4 * 16 * 2


def test_compress_layer_flops_counts_every_pass():
    job = {"ratio": 0.6, "rank_multiple": 8, "refine_epochs": 5}
    seq, seqs = 8, 2
    t = seq * seqs
    att = 4 * 4 * 16 * (seq + 1) / 2
    dense = 2 * Y.layer_params(M) + att
    comp = 2 * Y.layer_params(M, 0.6, 8) + att
    qkv = 2 * Y.layer_params(M, 0.6, 8, factorized=["wq", "wk", "wv"]) + att
    qkvo = 2 * Y.layer_params(M, 0.6, 8,
                              factorized=["wq", "wk", "wv", "wo"]) + att
    fin = 2 * Y.layer_params(M, 0.6, 8, factorized=[
        "wq", "wk", "wv", "wo", "gate", "up"]) + att
    want = t * (4 * dense + dense + qkv + qkvo + fin)   # calibration
    want += t * dense + t * comp * (3 * 5 + 3)
    want += Y.cov_flops_bytes(M, t)[0] + Y.solve_flops(M, 0.6, 8)
    assert Y.compress_layer_flops(M, job, seq, seqs) == pytest.approx(want)


def test_peaks():
    p = Y.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        Y.peaks("cpu")


def test_traced_layers():
    from types import SimpleNamespace as NS
    counts = {"solve_anchored": 7, "update_covs": 24}
    trace = NS(module_count=lambda names: counts[names[0]])
    run = NS(model=M, trace=trace, cell=NS(traffic={
        "calib_sequences": 12, "compress": {"microbatch": 2}}))
    assert Y.traced_layers(run) == 1.0                   # 7 linears a layer
    assert Y.traced_layers(run, "update_covs") == 1.0    # 4 groups x 6
