"""Queries answered inside the window per second: their count over the
window's seconds. A stall at the end of the window that no answer follows
counts in full."""


def read(run):
    w = run.window
    n = sum(1 for q in w.queries if q.ok and q.t_recv <= w.seconds)
    return n / w.seconds if n else None
