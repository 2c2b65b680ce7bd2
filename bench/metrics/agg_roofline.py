"""agg_roofline: the least time of an aggregation, the larger of its FLOPs
over the bf16 peak and its least HBM bytes over the HBM peak
(``bench.counts``; the bytes bound applies at these shapes), over the
traced window's time per aggregation, in percent."""
from bench import counts, peaks


def read(cell, out):
    if out.trace is None:
        return None
    tr = cell.traffic
    it = tr["aggregator"]["rpca_iters"]
    least, _ = counts.roofline_time(
        counts.agg_flops(cell.config, tr["clients"], it),
        counts.agg_least_bytes(cell.config, tr["clients"], it),
        peaks.peaks(out.device["kind"]), cell.chips)
    return 100.0 * least * out.facts["rounds"] / out.trace["window_s"]
