"""moe_s.hybrid: device seconds a round under the ``local.moe.*`` scopes
(routing, the held experts with their adapters, the shared expert), over
the traced window's rounds (``bench/scopes.py``)."""


def read(cell, out):
    if out.trace is None or "scopes" not in out.trace:
        return None
    secs = [v for k, v in out.trace["scopes"].items() if "/local.moe." in k]
    return sum(secs) / out.facts["rounds"] if secs else None
