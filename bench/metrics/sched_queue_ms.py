"""Mean admission-queue wait per request (``BatchAccounting.sched_queue_ns``
over the requests of every batch of the window), ms."""


def read(run):
    b = run.batches()
    n = sum(getattr(a, "batch_size", 0) for a in b)
    if not n:
        return None
    return sum(getattr(a, "sched_queue_ns", 0) for a in b) / n / 1e6
