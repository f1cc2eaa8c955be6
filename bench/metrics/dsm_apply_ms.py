"""Mean time ``dsm_batch`` takes per applied MOVE/MERGE (host clock around
the benchmark's own group-committed call), ms."""


def read(run):
    w = run.window
    n = w.groups[-1] if w.groups else 0
    if not n:
        return None
    return 1e3 * sum(w.apply_s) / n
