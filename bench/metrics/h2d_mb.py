"""Mean host bytes handed to the device per batch
(``BatchAccounting.h2d_bytes``), MB (10^6 bytes). None where the program
does not count them."""


def read(run):
    sizes = [getattr(a, "h2d_bytes", None) for a in run.batches()]
    if not sizes or None in sizes:
        return None
    return sum(sizes) / len(sizes) / 1e6
