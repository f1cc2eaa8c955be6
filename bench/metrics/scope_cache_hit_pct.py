"""Share of the unique scopes of each batch that the planner's mask cache
served (``scope_cache_hits`` over ``unique_scopes``), %."""


def read(run):
    b = run.batches()
    n = sum(getattr(a, "unique_scopes", 0) for a in b)
    if not n:
        return None
    return 100.0 * sum(getattr(a, "scope_cache_hits", 0) for a in b) / n
