"""Share of the chip's HBM bandwidth that a decode step reaches: the bytes
a step must read (every layer's factors and the head, and the filled
positions of the cache, ``yardstick``) over the mean device time of the
decode program (``serve_step``)."""

from bench import yardstick

PROGRAMS = ("serve_step",)


def read(run):
    if run.trace is None or not run.peaks:
        return None
    t = run.trace.module_seconds(PROGRAMS)
    steps = run.trace.module_count(PROGRAMS)
    if not t or not steps:
        return None
    sv = run.cell.config["serve"]
    m = run.model
    latent = not m["qk_norm"]
    keys, _ = yardstick.decode_contexts(run.counters["requests"])
    n_steps = len(run.counters["decode_step_times"])
    cache = keys * m["num_layers"] * yardstick.cache_bytes_per_token(
        m, sv["ratio"], sv["rank_multiple"], latent) / n_steps
    per_step = yardstick.decode_weight_bytes(
        m, sv["ratio"], sv["rank_multiple"]) + cache
    return 100.0 * per_step / (t / steps) / run.peaks["hbm_bytes_per_s"]
