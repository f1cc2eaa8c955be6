#!/usr/bin/env python3
"""Drive the directory-scoped serving path once on a TPU and check its answers.

    python chip_smoke.py             # one chip: five serving phases + DSM
    python chip_smoke.py --chips 4   # four chips: the sharded executor only

One chip: a WIKI-Dir twin at the published 1024-d width (``make_wiki_dir(
scale=0.25)``: ~485k entries, ~91k directories, a ~2 GB fp32 store plus its
int8 and PQ tiers) is ingested into ``DirectoryVectorDB(scope_strategy=
"triehi")`` and served through ``ScheduledDSQ`` (threaded collector/executor,
``degrade=False``) in five configurations — flat fp32 on the jnp twin, flat
fp32 / int8 / PQ (default rescore windows) on the Pallas scan kernels, and
IVF (sqrt(n) lists) on the Pallas list-scan kernel twice: at the default
probe depth and probing every list. Then one MOVE and one MERGE from the
dataset's DSM ops go through ``dsm_batch`` and every configuration serves
again. Each answer is checked against a plain NumPy fp32 brute force over
the scope, recomputed on the moved tree: the exact paths must return the
reference top-10 (ties within the fp32 tolerance allowed), int8 must reach
recall@10 >= 0.99, PQ >= 0.95, and IVF probing every list >= 0.95. IVF at
the default probe depth must return the exact top-10 of the in-scope rows
of the lists it probed (its recall against the whole scope is printed, not
gated: on this near-isotropic twin few of a scope's neighbours share a
query's nearest lists).

Four chips (``--chips 4``): the same store rows are sharded over a 4-device
mesh by ``ShardedExecutor`` and served through ``ScheduledDSQ``; the answers
are compared with flat fp32 on device 0 and with the NumPy reference.

Any failure — no TPU, a kernel in interpret mode, a failed batch, a staging
fault absorbed, a breaker trip, health other than ``healthy``, a missed
check — exits non-zero without the final line. On success the last line of
standard output is ``{"ok": true, "device": {...}}``; the lines before it
(set-up and compile seconds, batch counts, recall, device memory) are
informative only.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parent
K = 10
BATCH = 64
EXACT_ATOL = 1e-6      # |score| <= 1 (unit vectors): ~8 fp32 ULP at 1.0
FLOORS = {"int8": 0.99, "pq": 0.95, "ivf": 0.95}


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------- reference
class Reference:
    """Plain NumPy fp32 brute force over each request's scope. Directory
    membership is string-prefix matching on the entries' directory paths,
    rewritten in place by MOVE and MERGE — no scope index involved."""

    def __init__(self, vectors: np.ndarray, entry_paths: Sequence[str]):
        self.vectors = vectors
        self.dirs = np.asarray(entry_paths)
        self._cache: Dict[Tuple[int, str, bool], Tuple] = {}
        self._scopes: Dict[Tuple[str, bool], np.ndarray] = {}

    def _rekey(self, old: str, new: str) -> None:
        under = np.flatnonzero(np.char.startswith(self.dirs, old))
        moved = [new + d[len(old):] for d in self.dirs[under].tolist()]
        self.dirs = self.dirs.astype(object)
        self.dirs[under] = moved
        self.dirs = self.dirs.astype(str)
        self._cache.clear()
        self._scopes.clear()

    def move(self, src: str, new_parent: str) -> None:
        name = src.rstrip("/").rsplit("/", 1)[-1]
        self._rekey(src, new_parent + name + "/")

    def merge(self, src: str, dst: str) -> None:
        self._rekey(src, dst)

    def scope(self, anchor: str, recursive: bool) -> np.ndarray:
        key = (anchor, recursive)
        if key not in self._scopes:
            hit = (np.char.startswith(self.dirs, anchor) if recursive
                   else self.dirs == anchor)
            self._scopes[key] = np.flatnonzero(hit)
        return self._scopes[key]

    @staticmethod
    def _top(scores: np.ndarray, ids: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray]:
        """(K,) best scores/ids, ties by lower id; -inf / -1 padded."""
        kk = min(K, len(ids))
        top = np.argpartition(-scores, kk - 1)[:kk] if kk else ids[:0]
        order = top[np.lexsort((ids[top], -scores[top]))]
        out_s = np.full(K, -np.inf, np.float32)
        out_i = np.full(K, -1, np.int64)
        out_s[:kk], out_i[:kk] = scores[order], ids[order]
        return out_s, out_i

    def topk_within(self, queries: np.ndarray,
                    reqs: Sequence[Tuple[int, str, bool]],
                    allowed: Sequence[np.ndarray]
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Like :meth:`topk`, with each request's candidates cut to the
        rows of ``allowed[i]`` (e.g. the IVF lists it probed)."""
        out = [self._top(self.vectors[ids] @ queries[qi], ids)
               for (qi, anchor, rec), a in zip(reqs, allowed)
               for ids in [np.intersect1d(self.scope(anchor, rec), a)]]
        return (np.stack([o[0] for o in out]), np.stack([o[1] for o in out]))

    def topk(self, queries: np.ndarray, reqs: Sequence[Tuple[int, str, bool]]
             ) -> Tuple[np.ndarray, np.ndarray]:
        """(scores, ids) (len(reqs), K) for requests (query row, anchor,
        recursive); -inf / -1 past the scope size."""
        groups: Dict[Tuple[str, bool], List[int]] = {}
        for qi, anchor, rec in reqs:
            if (qi, anchor, rec) not in self._cache:
                groups.setdefault((anchor, rec), []).append(qi)
        for (anchor, rec), qis in groups.items():
            ids = self.scope(anchor, rec)
            scores = self.vectors[ids] @ queries[qis].T        # (|S|, g)
            for j, qi in enumerate(qis):
                self._cache[(qi, anchor, rec)] = self._top(scores[:, j], ids)
        rows = [self._cache[r] for r in reqs]
        return (np.stack([r[0] for r in rows]), np.stack([r[1] for r in rows]))


def recall_at_k(got_ids: np.ndarray, got_scores: np.ndarray,
                ref_ids: np.ndarray, ref_scores: np.ndarray) -> float:
    """Mean recall@K over requests with a non-empty scope. A missed
    reference id still counts when its score ties the worst returned score
    (a k-boundary tie resolved the other way)."""
    hits = total = 0
    for gi, gs, ri, rs in zip(got_ids, got_scores, ref_ids, ref_scores):
        live = ri >= 0
        if not live.any():
            continue
        found = set(gi[gi >= 0].tolist())
        worst = float(np.min(gs[gi >= 0])) if found else -np.inf
        hits += sum(1 for w, s in zip(ri[live].tolist(), rs[live].tolist())
                    if w in found or abs(s - worst) <= EXACT_ATOL)
        total += int(live.sum())
    return hits / max(total, 1)


# ------------------------------------------------------------------ serving
def _device_mb(dev) -> str:
    stats = dev.memory_stats() or {}
    used = stats.get("bytes_in_use")
    return "n/a" if used is None else f"{used / 2**20:.0f}"


def serve(db, queries: np.ndarray, reqs: Sequence[Tuple[int, str, bool]],
          batch: int, **config):
    """Serve ``reqs`` through a threaded ``ScheduledDSQ`` in batches of
    ``batch``; returns (scores (n, K), ids (n, K), per-batch seconds, plan
    labels). Any failed request, absorbed staging fault, breaker trip or
    health change is a :class:`SmokeFailure`."""
    from repro.serving.scheduler import ScheduledDSQ, SchedulerConfig
    sched = ScheduledDSQ(db, k=K, degrade=False,
                         cfg=SchedulerConfig(max_batch=batch,
                                             max_wait_ms=60_000.0),
                         **config)
    scores, ids, secs, plans = [], [], [], set()
    with sched:
        for lo in range(0, len(reqs), batch):
            chunk = reqs[lo: lo + batch]
            t0 = time.perf_counter()
            tickets = [sched.submit(queries[qi], anchor, recursive=rec)
                       for qi, anchor, rec in chunk]
            for t in tickets:
                res = t.result(timeout=900.0)
                scores.append(res.scores[0])
                ids.append(res.ids[0])
                plans.add(res.plan)
            secs.append(time.perf_counter() - t0)
    core = sched.scheduler
    snap = sched.metrics.snapshot()
    problems = []
    if snap["failed"] or core.last_batch_error is not None:
        problems.append(f"failed requests {snap['failed']}: "
                        f"{core.last_batch_error!r}")
    if core.stage_faults:
        problems.append(f"{core.stage_faults} staging faults absorbed")
    if snap["degrades"] or core.breaker.trips or core.breaker.open:
        problems.append("circuit breaker tripped")
    if sched.health != "healthy":
        problems.append(f"health {sched.health}")
    if snap["batches"] != len(secs):
        problems.append(f"{snap['batches']} batches for {len(secs)} "
                        f"submitted groups")
    if not plans <= {"gather", "scan", "empty", "ivf", "sharded"}:
        problems.append(f"per-request fallback path served: {plans}")
    if problems:
        raise SmokeFailure("; ".join(problems))
    return (np.stack(scores), np.stack(ids).astype(np.int64), secs,
            sorted(plans))


def run_phase(label: str, db, ref: Reference, queries, reqs, dev, *,
              kernel: Optional[str] = None, check: str = "exact",
              batch: int = BATCH, expect: Optional[Tuple] = None,
              **config) -> Dict[str, object]:
    """Serve ``reqs`` with ``config`` and check the answers: ``check`` is
    "exact" (the reference top-10, or ``expect`` when given) or a key of
    FLOORS (recall@10 against the reference). ``kernel`` is a launch key of
    ``ops.launch_counts()`` ("<kernel>:compiled") that must grow."""
    from repro.kernels import ops
    from repro.vectordb.parity import topk_mismatch
    before = ops.launch_counts().get(kernel, 0)
    t0 = time.perf_counter()
    scores, ids, secs, plans = serve(db, queries, reqs, batch, **config)
    wall = time.perf_counter() - t0
    launched = ops.launch_counts().get(kernel, 0) - before
    if kernel is not None and launched <= 0:
        raise SmokeFailure(f"{label}: no {kernel} launch")
    ref_s, ref_i = ref.topk(queries, reqs)
    rec = recall_at_k(ids, scores, ref_i, ref_s)
    want_s, want_i = expect if expect is not None else (ref_s, ref_i)
    live = (want_i >= 0) & (ids >= 0)
    diff = float(np.max(np.abs(scores[live] - want_s[live]), initial=0.0))
    line = (f"phase={label} batches={len(secs)} requests={len(reqs)} "
            f"first_batch_s={secs[0]:.3f} "
            f"steady_batch_s={np.mean(secs[1:]) if len(secs) > 1 else 0:.3f} "
            f"wall_s={wall:.3f} recall@10={rec:.4f} "
            f"max_abs_score_diff={diff:.3g} plans={','.join(plans)} "
            f"device_mb_in_use={_device_mb(dev)}")
    if kernel is not None:
        line += f" pallas={kernel} launches={launched}"
    log(line)
    if check == "exact":
        err = topk_mismatch(scores, ids, want_s, want_i, rtol=0.0,
                            atol=EXACT_ATOL)
        if err is not None:
            raise SmokeFailure(f"{label}: differs from the reference: {err}")
    elif rec < FLOORS[check]:
        raise SmokeFailure(f"{label}: recall@10 {rec:.4f} < "
                           f"{FLOORS[check]}")
    return {"scores": scores, "ids": ids}


# ------------------------------------------------------------------- set-up
def build(scale: float, seed: int, n_batches: int):
    from repro.datasets import make_wiki_dir
    from repro.vectordb import DirectoryVectorDB
    t0 = time.perf_counter()
    ds = make_wiki_dir(scale=scale, dim=1024, n_queries=n_batches * BATCH,
                       seed=seed)
    t1 = time.perf_counter()
    db = DirectoryVectorDB(dim=1024, scope_strategy="triehi")
    db.ingest(ds.vectors, ds.entry_paths)
    db.build_ann("flat")
    db.store.device_vectors().block_until_ready()
    t2 = time.perf_counter()
    log(f"setup dataset=wiki-dir scale={scale} dim=1024 "
        f"entries={ds.n_entries} dirs={len(ds.dirs)} "
        f"fp32_store_mb={db.store.nbytes() / 2**20:.0f} "
        f"generate_s={t1 - t0:.1f} ingest_s={t2 - t1:.1f}")
    reqs = [(i, ds.query_anchors[i], bool(ds.query_recursive[i]))
            for i in range(len(ds.queries))]
    return ds, db, Reference(ds.vectors, ds.entry_paths), reqs


def dsm_ops(db, ds) -> Tuple[Tuple[str, str], Tuple[str, str]]:
    """The first of the dataset's MOVE templates that applies to the live
    tree, and the first MERGE template still valid once that MOVE has
    applied. Templates are sampled over the generated tree, whose
    entry-less directories never reach the index, so both ends must be live
    directories, neither an ancestor of the other, and the MERGE must stay
    clear of the moved subtree."""
    from repro.core import paths as P
    live = {P.to_str(d) for d in db.namespaces["fs"].list_dirs()}

    def apart(a: str, b: str) -> bool:
        return (a in live and b in live and a != "/"
                and not a.startswith(b) and not b.startswith(a))

    move = next(((src, dst) for src, dst in ds.moves if apart(src, dst)),
                None)
    if move is None:
        raise SmokeFailure("no MOVE template applies to the live tree")
    src, parent = move
    for m_src, m_dst in ds.merges:
        if apart(m_src, m_dst) and not any(
                e.startswith(src) or src.startswith(e)
                for e in (m_src, m_dst)):
            return move, (m_src, m_dst)
    raise SmokeFailure("no MERGE template survives the MOVE")


def apply_dsm(db, ds, ref: Reference) -> None:
    (src, parent), (m_src, m_dst) = dsm_ops(db, ds)
    moved = len(ref.scope(src, True))
    merged = len(ref.scope(m_src, True))
    t0 = time.perf_counter()
    res = db.dsm_batch([("move", src, parent), ("merge", m_src, m_dst)])
    if any(e is not None for e in res.errors):
        raise SmokeFailure(f"dsm_batch rejected ops: {res.errors}")
    ref.move(src, parent)
    ref.merge(m_src, m_dst)
    log(f"dsm move={src}->{parent} merge={m_src}->{m_dst} "
        f"moved_entries={moved} merged_entries={merged} "
        f"apply_s={time.perf_counter() - t0:.3f}")


# ------------------------------------------------------------------- checks
def check_one_chip(dev, scale: float = 0.25, seed: int = 0,
                   n_batches: int = 3, interpret: bool = False) -> None:
    """The one-chip phases. ``interpret=True`` rehearses them on the CPU at
    a small ``scale``: every Pallas launch must then be interpreted, where
    on the chip every one must be compiled."""
    import math

    from repro.kernels import ops
    from repro.vectordb.costmodel import model_of
    mode = "interpret" if interpret else "compiled"
    if ops.interpret_mode() != interpret:
        raise SmokeFailure(f"Pallas kernels would not run {mode}")
    log(f"setup pallas={mode}")
    ds, db, ref, reqs = build(scale, seed, n_batches)
    t0 = time.perf_counter()
    db.store.device_q_vectors()
    db.store.device_pq_codes()
    log(f"setup int8_pq_tiers_s={time.perf_counter() - t0:.1f} "
        f"pq_m={db.store.pq_m} device_mb_in_use={_device_mb(dev)}")
    t0 = time.perf_counter()
    n_lists = max(1, round(math.sqrt(len(db.store))))
    db.build_ann("ivf", n_lists=n_lists)
    ivf = db.executors["ivf"]
    blocks = ivf.list_blocks()
    blocks.rows.block_until_ready()
    nprobe = model_of(db.store).default_nprobe(n_lists)
    log(f"setup ivf n_lists={n_lists} default_nprobe={nprobe} "
        f"max_list={ivf.partition_stats()['max_len']} "
        f"list_blocks={blocks.ids.shape[0]} "
        f"build_s={time.perf_counter() - t0:.1f} "
        f"device_mb_in_use={_device_mb(dev)}")

    def probed(qs):
        """Rows of the lists each request probes (the device probe, checked
        against NumPy distances within fp32 rounding)."""
        probe = np.concatenate([ivf.probe(qs[lo: lo + BATCH], nprobe)
                                for lo in range(0, len(qs), BATCH)])
        q64, c64 = qs.astype(np.float64), ivf.centers.astype(np.float64)
        d2 = ((q64 * q64).sum(1)[:, None] - 2.0 * q64 @ c64.T
              + (c64 * c64).sum(1)[None, :])
        got = np.take_along_axis(d2, probe, axis=1).max(axis=1)
        rest = d2.copy()
        np.put_along_axis(rest, probe, np.inf, axis=1)
        if np.any(got > rest.min(axis=1) + 1e-4):
            raise SmokeFailure("the device probe skipped a nearer list")
        lists = ivf.lists
        return [np.concatenate([lists[c] for c in row]).astype(np.int64)
                for row in probe]

    k = lambda name: f"{name}:{mode}"              # noqa: E731
    phases = [
        ("flat_fp32_jnp", dict(check="exact")),
        ("flat_fp32_pallas", dict(check="exact", use_pallas=True,
                                  kernel=k("masked_topk"))),
        ("flat_int8_pallas", dict(check="int8", use_pallas=True,
                                  precision="int8",
                                  kernel=k("masked_topk_i8"))),
        ("flat_pq_pallas", dict(check="pq", use_pallas=True, precision="pq",
                                kernel=k("masked_topk_pq"))),
        (f"ivf_fp32_pallas_nprobe{nprobe}",
         dict(check="exact", use_pallas=True, executor="ivf",
              kernel=k("ivf_gather_topk"), probed=True)),
        (f"ivf_fp32_pallas_nprobe{n_lists}",
         dict(check="ivf", use_pallas=True, executor="ivf", nprobe=n_lists,
              kernel=k("ivf_gather_topk"))),
    ]
    qs = np.asarray([ds.queries[qi] for qi, _, _ in reqs], np.float32)
    for tag in ("", "_after_dsm"):
        if tag:
            apply_dsm(db, ds, ref)
        for label, cfg in phases:
            cfg = dict(cfg)
            if cfg.pop("probed", False):
                cfg["expect"] = ref.topk_within(ds.queries, reqs, probed(qs))
            run_phase(label + tag, db, ref, ds.queries, reqs, dev, **cfg)


def check_four_chips(dev, scale: float = 0.25, seed: int = 0,
                     n_batches: int = 3) -> None:
    from repro.vectordb.parity import topk_mismatch
    ds, db, ref, reqs = build(scale, seed, n_batches)
    t0 = time.perf_counter()
    db.build_ann("sharded")
    ex = db.executors["sharded"]
    ex.sync()
    ex.view.db.block_until_ready()
    shard_devs = {s.device for s in ex.view.db.addressable_shards}
    log(f"setup sharded n_shards={ex.n_shards} devices={len(shard_devs)} "
        f"rows_per_shard={ex.view.n_loc} "
        f"upload_s={time.perf_counter() - t0:.1f}")
    if ex.n_shards != 4 or len(shard_devs) != 4:
        raise SmokeFailure(f"rows not spread over 4 chips: n_shards="
                           f"{ex.n_shards}, devices={len(shard_devs)}")
    flat = run_phase("flat_fp32_device0", db, ref, ds.queries, reqs, dev,
                     check="exact")
    sharded = run_phase("sharded_fp32_4chips", db, ref, ds.queries, reqs,
                        dev, check="exact", executor="sharded")
    err = topk_mismatch(sharded["scores"], sharded["ids"], flat["scores"],
                        flat["ids"], rtol=0.0, atol=EXACT_ATOL)
    if err is not None:
        raise SmokeFailure(f"sharded differs from flat: {err}")
    log("sharded_vs_flat=match")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded 4-chip path")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing ({e})",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); refusing to "
              f"run on another backend", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"compile_cache={enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        (check_four_chips if args.chips == 4 else check_one_chip)(dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
