"""mfu.round: model FLOPs of the traced window's local phases (forward and
the backward LoRA needs, ``bench.counts.round_flops``) over the traced
window and the chips' bf16 peak, in percent."""
from bench import counts, peaks


def read(cell, out):
    if out.trace is None:
        return None
    peak = peaks.peaks(out.device["kind"])["bf16_flops"]
    flops = counts.round_flops(cell.config, cell.traffic) * out.facts["rounds"]
    return 100.0 * flops / (out.trace["window_s"] * cell.chips * peak)
