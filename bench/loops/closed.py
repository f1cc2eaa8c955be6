"""Closed loop: a fixed number of clients, each sending its next query as
soon as its last one is answered, with MOVE/MERGE ops mixed in as a share
of the operations.

Parameters (``bench/traffic/<name>.json`` with ``"loop": "closed"``):

- ``clients``: how many queries are in flight.
- ``pool``: how many query templates the configuration's structure seed
  draws (``twin.query_pool``: ``anchor_zipf``, ``recursive_share``).
  The n-th query sent takes template ``n mod pool``, so every seed serves
  the same set of queries. ``--seed`` orders a pool larger than
  ``clients``; a pool of at most ``clients`` keeps its order, since each
  client then keeps one template and another order would regroup the
  batches for the whole run, changing the work.
- ``dsm_share``: the share of operations that are MOVE/MERGE. One op, drawn
  in order by ``twin.draw_ops`` (``shallow_depth``), falls due after every
  ``(1 - dsm_share) / dsm_share`` queries sent, and is applied at the next
  maintenance slot. The pool carries ``pool`` queries' worth of ops.
"""
from __future__ import annotations

import collections
import threading
import time
from dataclasses import dataclass
from typing import List, Tuple

import numpy as np

from benchlib import load, twin

ROWS = 16384                    # query vectors drawn per run


@dataclass
class Stream:
    templates: twin.QueryTemplate   # in this seed's order
    anchors: twin.Anchors           # each template's anchor as ops apply
    dsm: List[load.DsmOp]           # in the order they fall due
    every: int                      # queries per op (0: no ops)
    entries: np.ndarray             # entry behind each query-vector row

    def query(self, n: int, applied: int, t: float) -> load.Query:
        """The n-th query, sent ``t`` seconds into the window on the tree
        the first ``applied`` ops left."""
        tm, i = self.templates, n % len(self.templates)
        anchor = self.anchors.at(applied, int(tm.entry[i]),
                                 float(tm.level[i]))
        return load.Query(n % len(self.entries), anchor,
                          bool(tm.recursive[i]), t)

    def op_due_after(self, n: int) -> bool:
        return bool(self.every) and (n + 1) % self.every == 0


def build_stream(cfg: dict, traffic: dict, corpus: twin.Corpus,
                 seed: int) -> Stream:
    ss = int(cfg["structure_seed"])
    pool = twin.query_pool(corpus, traffic, int(traffic["pool"]), ss)
    if len(pool) > int(traffic["clients"]):
        rng = np.random.default_rng([int(seed) % (1 << 64), 5])
        pool = pool.take(rng.permutation(len(pool)))
    share = float(traffic.get("dsm_share", 0.0))
    every = int(round((1.0 - share) / share)) if share > 0 else 0
    n_ops = -(-len(pool) // every) if every else 0
    ops = twin.draw_ops(corpus.primary, traffic, n_ops, ss)
    dsm = [load.DsmOp(o.kind, o.src_path, o.dst_path) for o in ops]
    rows = max(ROWS // len(pool), 1) * len(pool)
    return Stream(pool, twin.Anchors(corpus.primary, ops), dsm, every,
                  np.resize(pool.entry, rows))


def drive(sched, slot: load.DsmSlot, st: Stream, qvecs: np.ndarray,
          traffic: dict, seconds: float, grace: float) -> load.Window:
    """``clients`` clients for ``seconds``; then up to ``grace`` seconds
    for the last answers and the ops that fell due."""
    t0 = load.clock()
    slot.t0 = t0
    deadline = t0 + seconds + grace
    out: List[load.Query] = []
    live: "collections.deque[load.Query]" = collections.deque()

    def send():
        n = len(out)
        now = load.clock() - t0
        q = st.query(n, slot.next, now)
        out.append(q)
        if load.submit(sched, slot, q, qvecs, t0):
            live.append(q)
        if st.op_due_after(n):
            slot.make_due(now)

    def loop():
        for _ in range(int(traffic["clients"])):
            send()
        while live:
            q = live.popleft()
            load.receive(q, deadline, t0)
            if load.clock() - t0 < seconds:
                send()

    t = threading.Thread(target=loop, name="bench-clients")
    t.start()
    with load.span("bench.window"):
        time.sleep(max(t0 + seconds - load.clock(), 0.0))
    t.join()
    while not slot.settled() and load.clock() < deadline:
        time.sleep(0.01)
    return load.Window(seconds, out, slot.ops[: slot.due], slot.groups,
                       slot.apply_s, t0=t0)


def timeline(st: Stream, n: int
             ) -> Tuple[List[Tuple[str, str, str]], np.ndarray,
                        List[Tuple[str, bool]]]:
    """The first ``n`` queries as one sequence, for the control: the ops
    that fell due among them as path triples, a flag per event (True: the
    next op), and each query's ``(anchor, recursive)`` on the tree the ops
    before it left. Query i uses row i of the query vectors."""
    if n > len(st.entries):
        raise ValueError(f"{n} queries, {len(st.entries)} query vectors")
    flags: List[bool] = []
    qs: List[Tuple[str, bool]] = []
    applied = 0
    for i in range(n):
        q = st.query(i, applied, 0.0)
        qs.append((q.anchor, q.recursive))
        flags.append(False)
        if st.op_due_after(i) and applied < len(st.dsm):
            flags.append(True)
            applied += 1
    ops = [(o.kind, o.src, o.dst) for o in st.dsm[:applied]]
    return ops, np.asarray(flags, bool), qs
