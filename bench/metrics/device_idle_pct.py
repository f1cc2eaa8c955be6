"""Share of the traced window in which no operation ran on the device
(1 - union of op intervals / window), %."""


def read(run):
    r = run.reduction
    return None if r is None else 100.0 * r.idle_share
