"""Device: share of the traced window in which no operation ran on the
device, in % (1 - union of operation intervals / window)."""


def read(v):
    if v.trace is None or v.trace["idle_share"] is None:
        return None
    return 100.0 * v.trace["idle_share"]
