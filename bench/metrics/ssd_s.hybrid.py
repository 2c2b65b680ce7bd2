"""ssd_s.hybrid: device seconds a round under the ``local.ssd`` scope (the
Mamba-2 mixers, forward, recomputation and backward), over the traced
window's rounds (``bench/scopes.py``)."""


def read(cell, out):
    if out.trace is None or "scopes" not in out.trace:
        return None
    secs = [v for k, v in out.trace["scopes"].items() if k.endswith("/local.ssd")]
    return sum(secs) / out.facts["rounds"] if secs else None
