"""mfu.agg: Gram and reconstruction FLOPs of the traced window's
aggregations (``bench.counts.agg_flops``) over the traced window and the
chips' bf16 peak, in percent."""
from bench import counts, peaks


def read(cell, out):
    if out.trace is None:
        return None
    tr = cell.traffic
    peak = peaks.peaks(out.device["kind"])["bf16_flops"]
    flops = counts.agg_flops(cell.config, tr["clients"], tr["aggregator"]["rpca_iters"])
    return 100.0 * flops * out.facts["rounds"] / (out.trace["window_s"] * cell.chips * peak)
