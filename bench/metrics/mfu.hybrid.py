"""mfu.hybrid: model FLOPs of the traced window's local phases, for the
chip's expert share (``bench.counts_hybrid.round_flops``), over the traced
window and the chips' bf16 peak, in percent."""
from bench import counts_hybrid, peaks


def read(cell, out):
    if out.trace is None:
        return None
    peak = peaks.peaks(out.device["kind"])["bf16_flops"]
    flops = counts_hybrid.round_flops(cell.config, cell.traffic) * out.facts["rounds"]
    return 100.0 * flops / (out.trace["window_s"] * cell.chips * peak)
