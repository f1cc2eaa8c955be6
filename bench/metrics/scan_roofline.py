"""Share of the scan's bandwidth roofline: the least time the chip needs to
read, at its peak HBM bandwidth, every in-scope row of each batch once (the
union of the batch's scopes, from the reference's own scopes) plus the
queries, summed over the batches answered in the traced window, over the
device-busy time of that window, %. The same work whatever implements the
scan. Bound by bandwidth: the chip publishes no fp32 operation rate."""


def read(run):
    r, pk = run.reduction, run.peaks
    if r is None or pk is None or not run.least_bytes or r.busy_s <= 0:
        return None
    return 100.0 * run.least_bytes / pk.hbm_bytes_per_s / r.busy_s
