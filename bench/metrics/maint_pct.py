"""Share of the window in which the scheduler's executor thread ran the
maintenance slot instead of a batch: the foreground stall that structural
updates cause (the program's ``sched.maint`` spans, clipped to the window,
over the window), %. None where the program has no spans or its span ring
dropped records of the window."""


def read(run):
    try:
        from repro import tracing
    except ImportError:
        return None
    w = run.window
    spans = tracing.window(int(w.t0 * 1e9), int((w.t0 + w.seconds) * 1e9))
    if spans.dropped:
        return None
    return 100.0 * spans.clipped_ns("sched.maint") / (w.seconds * 1e9)
