"""Mean ranking time per batch (``BatchAccounting.ann_ns``, until the
results are on the host), ms."""


def read(run):
    b = run.batches()
    if not b:
        return None
    return sum(getattr(a, "ann_ns", 0) for a in b) / len(b) / 1e6
