"""What every load generator shares: requests, the maintenance slot, the
window, and submitting and receiving on the benchmark's own clock.

The benchmark times every request on its own clock (``time.perf_counter``)
and reads from the program only its answers. The generators themselves
(how requests and structural updates are laid out in time) are files of
their own, ``bench/loops/<loop>.py``. Structural updates enter through the
scheduler's maintenance slot (:class:`DsmSlot`), which runs between
batches, never during one.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

clock = time.perf_counter


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclass
class Query:
    vec: int                    # row of the query-vector matrix
    anchor: str
    recursive: bool
    t_sched: float              # seconds into the window
    t_sent: float = float("nan")
    t_recv: float = float("nan")
    ticket: object = None
    epoch: int = -1             # DSM groups applied before its batch ran
    ids: Optional[np.ndarray] = None
    scores: Optional[np.ndarray] = None
    plan: str = ""
    batch: object = None        # the batch's shared accounting
    error: str = ""

    @property
    def ok(self) -> bool:
        return self.ids is not None


@dataclass
class DsmOp:
    kind: str
    src: str
    dst: str
    t_sched: float = float("nan")   # seconds into the window it fell due
    t_done: float = float("nan")
    error: str = ""


class DsmSlot:
    """The maintenance callable: each time the slot opens, every op then
    due goes to ``dsm_batch`` as one group. The load generator makes ops
    due in order (:meth:`make_due`). Before a group applies, every query
    whose batch has already run is stamped with the number of groups
    before it, so each answer maps to one tree state."""

    def __init__(self, db, ops: Sequence[DsmOp], namespace: str):
        self.db = db
        self.ops = list(ops)
        self.namespace = namespace
        self.next = 0                        # ops applied
        self.due = 0                         # ops due
        self.groups: List[int] = []          # ops applied after each group
        self.apply_s: List[float] = []
        self.t0: Optional[float] = None
        self.lock = threading.Lock()
        self.unstamped: List[Query] = []

    def stamp(self, final: bool = False) -> None:
        epoch = len(self.groups)
        keep = []
        for q in self.unstamped:
            if final or q.ticket.done():
                q.epoch = epoch
            else:
                keep.append(q)
        self.unstamped = keep

    def make_due(self, t: float) -> None:
        """The next op falls due ``t`` seconds into the window (a no-op once
        the drawn ops run out)."""
        with self.lock:
            if self.due < len(self.ops):
                self.ops[self.due].t_sched = t
                self.due += 1

    def settled(self) -> bool:
        return self.next >= self.due

    def __call__(self) -> Optional[dict]:
        j = self.due
        if self.t0 is None or j <= self.next:
            return None
        group = self.ops[self.next: j]
        with self.lock:
            self.stamp()
        with span("bench.dsm"):
            t = clock()
            res = self.db.dsm_batch([(o.kind, o.src, o.dst) for o in group],
                                    namespace=self.namespace)
            done = clock()
        for o, err in zip(group, res.errors):
            o.t_done = done - self.t0
            o.error = "" if err is None else repr(err)
        self.apply_s.append(done - t)
        self.next = j
        self.groups.append(j)
        return {"applied": len(group)}


@dataclass
class Window:
    """What one measured window produced: every query sent, and every
    structural update that fell due."""
    seconds: float
    queries: List[Query]
    ops: List[DsmOp]
    groups: List[int]
    apply_s: List[float]
    t0: float = 0.0


def receive(q: Query, deadline: float, t0: float) -> None:
    try:
        with span("bench.wait"):
            res = q.ticket.result(timeout=max(deadline - clock(), 0.0))
    except Exception as e:                   # noqa: BLE001 — any failure of
        q.error = repr(e)                    # a request counts as failed
        return
    q.t_recv = clock() - t0
    q.ids = np.asarray(res.ids[0], np.int64)
    q.scores = np.asarray(res.scores[0], np.float32)
    q.plan = res.plan
    q.batch = res.batch


def submit(sched, slot: DsmSlot, q: Query, qvecs: np.ndarray,
            t0: float) -> bool:
    with slot.lock:
        q.t_sent = clock() - t0
        try:
            with span("bench.submit"):
                q.ticket = sched.submit(qvecs[q.vec], q.anchor,
                                        recursive=q.recursive)
        except Exception as e:               # noqa: BLE001 — shed = failed
            q.error = repr(e)
            return False
        slot.unstamped.append(q)
    return True


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``p``
    percent of the sample at or below it (inf counts as largest)."""
    v = np.sort(np.asarray(values, np.float64))
    if len(v) == 0:
        return float("nan")
    return float(v[max(int(np.ceil(p / 100.0 * len(v))) - 1, 0)])
