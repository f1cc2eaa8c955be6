"""90th percentile over every MOVE/MERGE of the window, from its scheduled
time until ``dsm_batch`` returned. An op rejected or never applied counts
as the longest wait the run allowed."""
from benchlib.load import percentile


def read(run):
    ops = run.window.ops
    if not ops:
        return None
    worst = run.window.seconds + run.grace_s
    lat = [(o.t_done - o.t_sched) if (o.t_done == o.t_done and not o.error)
           else worst for o in ops]
    return 1e3 * percentile(lat, 90)
