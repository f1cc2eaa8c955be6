"""Program spans and counters on the serving path.

``span(name)`` times one stage of the work on the thread that does it. It
writes the stage three ways at once:

- a ``jax.profiler.TraceAnnotation`` (with the batch id as metadata), so a
  profiler trace holds it on a host plane, on the device ops' clock;
- a record ``(name, thread, start_ns, end_ns, batch)`` in :data:`RING`, a
  fixed-size in-process ring on ``time.perf_counter_ns``;
- with ``into=``, the duration added to a ``stage_ns`` dict under the last
  dotted part of the name (``dsm.apply`` -> ``stage_ns["apply"]``).

Nothing turns it on or off: with the profiler off a span costs a couple of
microseconds, so spans sit around stages, never inside a per-entry or
per-directory loop.

A batch of the serving path has one id, from :func:`new_batch`, which every
span of the batch carries and which ``BatchAccounting.seq`` repeats. The
scheduler opens :func:`batch` around the work of each batch on each of its
threads; spans opened there take its id, :func:`count_h2d` adds to its
host-to-device byte count and :func:`count_gather_rows` to its gathered
rows. Names never start with ``bench.``: that prefix belongs to the
benchmark's own spans.
"""
from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional

import jax

RING_SIZE = 1 << 18
NO_BATCH = -1


class Span(NamedTuple):
    name: str
    thread: str
    start_ns: int
    end_ns: int
    batch: int

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns


@dataclass
class Spans:
    """The spans of a ring that overlap ``[t0_ns, t1_ns)``, and how many
    records the ring dropped that may have overlapped it (0: none)."""
    t0_ns: int
    t1_ns: int
    spans: List[Span]
    dropped: int

    def clipped_ns(self, name: str) -> int:
        """Summed time of the spans called ``name`` inside the window."""
        return sum(min(s.end_ns, self.t1_ns) - max(s.start_ns, self.t0_ns)
                   for s in self.spans if s.name == name)


class Ring:
    """The newest ``size`` span records; older ones are overwritten and
    counted as dropped."""

    def __init__(self, size: int = RING_SIZE):
        self.size = size
        self._buf: List[Optional[tuple]] = [None] * size
        self._n = 0
        self._lock = threading.Lock()
        self.dropped = 0
        self._dropped_end = -1         # latest end_ns of a dropped record

    def append(self, rec: tuple) -> None:
        with self._lock:
            i = self._n % self.size
            old = self._buf[i]
            self._buf[i] = rec
            self._n += 1
            if old is not None:
                self.dropped += 1
                self._dropped_end = max(self._dropped_end, old[3])

    def window(self, t0_ns: int, t1_ns: int) -> Spans:
        with self._lock:
            recs = [r for r in self._buf if r is not None]
            lost = self.dropped if self._dropped_end > t0_ns else 0
        spans = sorted((Span(*r) for r in recs
                        if r[3] > t0_ns and r[2] < t1_ns),
                       key=lambda s: s.start_ns)
        return Spans(t0_ns, t1_ns, spans, lost)


class _Thread(threading.local):
    """Per thread: its name and the batch it is working on."""
    batch: Optional["Batch"] = None

    def __init__(self):
        self.name = threading.current_thread().name


RING = Ring()
_local = _Thread()
_batch_ids = itertools.count(1)


class Batch:
    """The batch a thread is working on: its id, the host bytes handed to
    the device for it so far, and the rows its exact fp32 gather groups
    took on the device and on the host."""
    __slots__ = ("seq", "h2d_bytes", "gather_rows_device", "gather_rows_host")

    def __init__(self, seq: int):
        self.seq = seq
        self.h2d_bytes = 0
        self.gather_rows_device = 0
        self.gather_rows_host = 0


def new_batch() -> int:
    """A fresh batch id (process-wide, increasing)."""
    return next(_batch_ids)


class batch:
    """Make batch ``seq`` (a fresh id when None and the thread has no
    batch; the thread's own batch when None and it has one) the current
    thread's batch until the block ends. Yields the :class:`Batch`."""
    __slots__ = ("seq", "_prev")

    def __init__(self, seq: Optional[int] = None):
        self.seq = seq

    def __enter__(self) -> Batch:
        self._prev = _local.batch
        if self.seq is None and self._prev is not None:
            cur = self._prev
        else:
            cur = Batch(new_batch() if self.seq is None else self.seq)
        _local.batch = cur
        return cur

    def __exit__(self, *exc) -> None:
        _local.batch = self._prev


def current_batch() -> int:
    b = _local.batch
    return NO_BATCH if b is None else b.seq


def count_h2d(nbytes: int) -> None:
    """Add ``nbytes`` host bytes handed to the device to the thread's
    batch (nothing outside a batch)."""
    b = _local.batch
    if b is not None:
        b.h2d_bytes += int(nbytes)


def count_gather_rows(n: int, on_device: bool) -> None:
    """Add ``n`` candidate rows of an exact fp32 gather group, taken by id
    on the device or copied on the host, to the thread's batch."""
    b = _local.batch
    if b is None:
        return
    if on_device:
        b.gather_rows_device += int(n)
    else:
        b.gather_rows_host += int(n)


class span:
    """Time a stage of the work: see the module docstring. ``batch``
    defaults to the current thread's batch. After the block, ``start_ns``,
    ``end_ns`` and ``ns`` hold its times."""
    __slots__ = ("name", "batch", "into", "start_ns", "end_ns", "_ann")

    def __init__(self, name: str, batch: Optional[int] = None,
                 into: Optional[Dict[str, int]] = None):
        self.name = name
        self.batch = current_batch() if batch is None else batch
        self.into = into

    def __enter__(self) -> "span":
        self._ann = (jax.profiler.TraceAnnotation(self.name)
                     if self.batch == NO_BATCH else
                     jax.profiler.TraceAnnotation(self.name,
                                                  batch=self.batch))
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        RING.append((self.name, _local.name, self.start_ns, self.end_ns,
                     self.batch))
        if self.into is not None:
            key = self.name.rsplit(".", 1)[-1]
            self.into[key] = self.into.get(key, 0) + self.ns

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns


def window(t0_ns: int, t1_ns: int) -> Spans:
    """The spans of :data:`RING` that overlap ``[t0_ns, t1_ns)``."""
    return RING.window(t0_ns, t1_ns)
