"""local_phase_s: mean host time of a round's local phase, dispatch to its
loss being ready (``t_local_s`` of ``fed.pipeline.run_rounds``), over the
traced window's rounds."""
import statistics


def read(cell, out):
    t = out.facts.get("t_local")
    return statistics.fmean(t) if t else None
