"""land_wait_s: mean host time blocked landing a round's aggregation
(``t_agg_s`` of ``fed.pipeline.run_rounds``), over the traced window's
rounds."""
import statistics


def read(cell, out):
    t = out.facts.get("t_agg")
    return statistics.fmean(t) if t else None
