"""Mean batch fill: ``sched_occupancy`` (requests over the scheduler's
max batch) per scheduler batch, %."""


def read(run):
    b = run.batches()
    n = sum(getattr(a, "sched_batches", 0) for a in b)
    if not n:
        return None
    return 100.0 * sum(getattr(a, "sched_occupancy", 0.0) for a in b) / n
