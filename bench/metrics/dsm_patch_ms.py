"""Mean time the planner's mask cache spends patching (and evicting) its
entries per applied MOVE/MERGE (the program's ``dsm.cache_patch`` spans
inside the benchmark's ``dsm_batch`` calls, over the ops those calls
applied), counting only the groups that ran wholly inside the window, ms.
None where the program has no spans, its span ring dropped records of the
window, or no group ran wholly inside it."""


def read(run):
    try:
        from repro import tracing
    except ImportError:
        return None
    w = run.window
    spans = tracing.window(int(w.t0 * 1e9), int((w.t0 + w.seconds) * 1e9))
    if spans.dropped:
        return None
    patches = [s for s in spans.spans if s.name == "dsm.cache_patch"]
    ns = applied = lo = 0
    for hi, apply_s in zip(w.groups, w.apply_s):
        group, lo = w.ops[lo:hi], hi
        done = group[-1].t_done
        if done - apply_s < 0 or done > w.seconds:
            continue
        a, b = (w.t0 + done - apply_s) * 1e9, (w.t0 + done) * 1e9
        ns += sum(s.ns for s in patches if s.start_ns >= a and s.end_ns <= b)
        applied += sum(1 for o in group if not o.error)
    return ns / applied / 1e6 if applied else None
