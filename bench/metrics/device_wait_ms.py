"""Mean time per batch that the executor blocks on device results (the
program's ``dsq.fetch`` spans inside the window, over the batches whose
executor spans fall in it), ms. None where the program has no spans or its
span ring dropped records of the window."""


def read(run):
    try:
        from repro import tracing
    except ImportError:
        return None
    w = run.window
    spans = tracing.window(int(w.t0 * 1e9), int((w.t0 + w.seconds) * 1e9))
    batches = {s.batch for s in spans.spans
               if s.name.startswith("dsq.") and s.batch >= 0}
    if spans.dropped or not batches:
        return None
    return spans.clipped_ns("dsq.fetch") / len(batches) / 1e6
