"""95th percentile of the latency of every query scheduled in the window,
on the benchmark's clock: from its scheduled send time until the answer is
in the client's hands. A query
that failed counts as the longest wait the run allowed."""
from benchlib.load import percentile


def read(run):
    w = run.window
    if not w.queries:
        return None
    worst = w.seconds + run.grace_s
    lat = [(q.t_recv - q.t_sched) if q.ok else worst for q in w.queries]
    return 1e3 * percentile(lat, 95)
