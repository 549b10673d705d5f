"""Share of its roofline that the ``cov_accum`` Pallas kernel reaches.

The least time is the larger of the products' FLOPs over the bf16 peak
and the bytes they need (both taps read once, three float32 products
written) over the HBM peak, from shapes (``yardstick.cov_flops_bytes``:
every group's statistics over every calibration token, once per layer in
sequential mode); it is compute bound at these widths.  The time is that
of the ``update_covs`` programs, the kernel and the add of its products
into the accumulators: the compression trace is read by program only
(its eigensolves run millions of small ops), so the share is a floor on
the kernel's own.
"""

from bench import yardstick

PROGRAMS = ("update_covs",)


def read(run):
    if run.trace is None or not run.peaks:
        return None
    t = run.trace.module_seconds(PROGRAMS)
    if not t:
        return None
    tr = run.cell.traffic
    tokens = tr["calib_sequences"] * tr["calib_tokens"]
    flops, byts = yardstick.cov_flops_bytes(run.model, tokens)
    layers = yardstick.traced_layers(run, "update_covs")
    least = max(flops / run.peaks["bf16_flops_per_s"],
                byts / run.peaks["hbm_bytes_per_s"]) * layers
    return 100.0 * least / t
