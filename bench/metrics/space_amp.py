"""Device bytes per corpus byte: the chip's peak bytes in use over the
run, over the live corpus at fp32 (rows x dim x 4)."""


def read(run):
    if not run.peak_bytes:
        return None
    return run.peak_bytes / run.corpus_bytes
