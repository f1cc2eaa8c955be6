"""Mean scope resolution and planning time per batch
(``BatchAccounting.directory_ns``), ms."""


def read(run):
    b = run.batches()
    if not b:
        return None
    return sum(getattr(a, "directory_ns", 0) for a in b) / len(b) / 1e6
