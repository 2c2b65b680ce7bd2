"""idle_share.round: share of the traced window in which no operation ran
on the device, averaged over the chips, in percent."""


def read(cell, out):
    if out.trace is None:
        return None
    return 100.0 * (1.0 - out.trace["busy_s"] / out.trace["window_s"])
