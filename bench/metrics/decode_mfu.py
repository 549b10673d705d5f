"""Share of the chip's bf16 peak that decoding reaches: the FLOPs a token
needs (2 × the factor and head params, plus attention at the window's
mean context, ``yardstick.decode_token_flops``) times the window's
decoded tokens per second."""

from bench import yardstick


def read(run):
    if not run.peaks:
        return None
    sv = run.cell.config["serve"]
    keys, decoded = yardstick.decode_contexts(run.counters["requests"])
    per_token = yardstick.decode_token_flops(
        run.model, sv["ratio"], sv["rank_multiple"], keys / max(decoded, 1))
    rate = run.end_to_end["decode_tokens_per_s"]
    return 100.0 * per_token * rate / run.peaks["bf16_flops_per_s"]
