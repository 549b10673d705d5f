"""Share of its roofline that the latent-cache ``flash_decode`` Pallas
kernel reaches in the decode program: the larger of its FLOPs over the
bf16 peak and its bytes over the HBM peak, for the filled positions only
(``yardstick.flash_decode_flops_bytes``), over the kernel's trace time."""

from bench import yardstick

KERNEL = r"flash_decode$"
PROGRAMS = ("serve_step",)


def read(run):
    if run.trace is None or not run.peaks:
        return None
    t = run.trace.op_seconds(KERNEL, PROGRAMS)
    if not t:
        return None
    sv = run.cell.config["serve"]
    m = run.model
    keys, decoded = yardstick.decode_contexts(run.counters["requests"])
    calls = len(run.counters["decode_step_times"]) * m["num_layers"]
    flops, byts = yardstick.flash_decode_flops_bytes(
        m, sv["ratio"], sv["rank_multiple"], keys * m["num_layers"],
        decoded * m["num_layers"], calls)
    least = max(flops / run.peaks["bf16_flops_per_s"],
                byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
