"""PQ/ADC tier + scope-aware tiered fp32 storage vs the fp32 exact scan.

Three sections, all gated with ``--smoke``:

* **Dataset twins** (hot/cold query skew via ``dirgen``'s ``anchor_zipf``
  knob): the 64-request mixed-scope serving batch from ``bench_quantized``,
  ranked at fp32 and at ``precision="pq"`` (uint8 ADC scan selects
  ``rescore_k`` candidates, exact fp32 gather-rescore ranks the final
  top-k). Gates: ``bytes_ratio`` (PQ code bytes / alive fp32 bytes)
  <= 0.08 and recall@10 >= 0.95 on both twins.
* **Tiered serving** on the same twins: the device byte budget is set
  below the fp32 store size, so the default-precision ``dsq_batch``
  auto-upgrades to the PQ scan and pulls only the rescore window's fp32
  rows host->device; the planner's cumulative scope heat then pins the
  hottest directories' rows on device. Gates: the upgrade actually
  happened (``db_bytes_pq`` accounted), every alive row is placed
  (pinned + host), the second batch fetches strictly fewer bytes than
  the first (hot pinning works under the Zipf anchor skew), and tiered
  recall@10 >= 0.95.
* **Scan wall-clock** on a corpus the twins are too small for
  (n=120k, 128-d at smoke scale, ADC at 1/32 of fp32 bytes): the PQ scan
  must beat the fp32 flat scan >= 2x *on every backend* — ADC is a LUT
  gather-accumulate, not a GEMM, so unlike ``bench_quantized`` there is
  no XLA:CPU int8-GEMM carve-out. Measured at B=4 queries per launch,
  the serving regime (per-scope planner groups are small; at B >> 8 the
  fp32 GEMM's MAC efficiency catches back up). Recall on this corpus is
  reported but not gated: tight synthetic clusters make top-10-vs-fp32 a
  tie-breaking exercise, and the quality gate lives on the twins above.

    PYTHONPATH=src python -m benchmarks.bench_pq [--scale S] \
        [--smoke] [--json out.json]
"""
from __future__ import annotations

import json
import time
from typing import Dict, List

import numpy as np

from repro.vectordb import DirectoryVectorDB

from .common import DIM, datasets

B = 64            # concurrent requests per serving batch
K = 10
N_UNIQUE = 8      # distinct scopes in the serving mix
REPEAT = 3        # timed batches per path (after one warmup)
SMOKE_SCALE = 0.01     # floor for --smoke: gates need n >> B*rescore
RESCORE_K = 8 * K      # twins' two-phase window (reported with the gate)
ANCHOR_ZIPF = 1.2      # hot/cold query-anchor skew on the twins

SCAN_N = 120_000       # wall-clock corpus rows at smoke scale
SCAN_N_FLOOR = 24_000
SCAN_DIM = 128
SCAN_M = 16            # 16 uint8 codes per 128-d row = 1/32 of fp32
SCAN_B = 4             # queries per scan launch (serving-regime batch)
SCAN_RESCORE_K = 320
SCAN_CENTERS = 64
SCAN_NOISE = 0.35


def _requests(ds, rng):
    anchors = list(dict.fromkeys(ds.query_anchors))[:N_UNIQUE - 1] + ["/"]
    paths = [anchors[i % len(anchors)] for i in range(B)]
    rec = [bool(i % 3) for i in range(B)]
    queries = ds.queries[rng.integers(0, len(ds.queries), size=B)]
    return queries.astype(np.float32), paths, rec


def _recall(base_res, other_res) -> float:
    hits = total = 0
    for a, b in zip(base_res, other_res):
        want = set(int(x) for x in a.ids[0] if int(x) >= 0)
        got = set(int(x) for x in b.ids[0] if int(x) >= 0)
        hits += len(want & got)
        total += len(want)
    return hits / max(total, 1)


def _clock(fn) -> float:
    fn()                                      # warmup (jit, cache fill)
    t0 = time.perf_counter_ns()
    for _ in range(REPEAT):
        fn()
    return (time.perf_counter_ns() - t0) / REPEAT / 1e3


def _scan_corpus(rng, n: int, dim: int) -> np.ndarray:
    """Clustered unit vectors (same shape as the twins' mixture, without
    the directory machinery) — big enough that the scan term dominates."""
    centers = rng.normal(size=(SCAN_CENTERS, dim)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    assign = rng.integers(0, SCAN_CENTERS, size=n)
    vecs = centers[assign] + SCAN_NOISE * rng.normal(
        size=(n, dim)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    return vecs.astype(np.float32)


def run(scale: float = SMOKE_SCALE, smoke: bool = False) -> List[Dict]:
    import jax
    if smoke:
        scale = max(scale, SMOKE_SCALE)
    rng = np.random.default_rng(0)
    rows = []

    # ---- dataset twins: bytes + recall gates, then tiered serving ------
    for ds_name, ds in datasets(scale, anchor_zipf=ANCHOR_ZIPF).items():
        db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi")
        db.ingest(ds.vectors, ds.entry_paths)
        db.build_ann("flat")
        queries, paths, rec = _requests(ds, rng)

        def fp32():
            return db.dsq_batch(queries, paths, k=K, recursive=rec)

        def pq():
            return db.dsq_batch(queries, paths, k=K, recursive=rec,
                                precision="pq", rescore_k=RESCORE_K)

        fp32_res, pq_res = fp32(), pq()
        recall = _recall(fp32_res, pq_res)
        n = len(db.store)
        bytes_ratio = db.store.pq_nbytes() / db.store.alive_nbytes()
        fp32_us, pq_us = _clock(fp32), _clock(pq)
        rows.append({
            "name": f"pq/{ds_name}/fp32",
            "us_per_call": fp32_us,
            "derived": f"n={n};db_mb={db.store.alive_nbytes() / 1e6:.2f}",
        })
        rows.append({
            "name": f"pq/{ds_name}/pq",
            "us_per_call": pq_us,
            "derived": (f"bytes_ratio={bytes_ratio:.4f};"
                        f"recall@{K}={recall:.4f};"
                        f"rescore_k={RESCORE_K};"
                        f"codebook_kb={db.store.pq_codebook_nbytes()/1e3:.1f};"
                        f"anchor_zipf={ANCHOR_ZIPF}"),
        })

        # tiered: fp32 rows no longer fit on device; the default-precision
        # batch auto-upgrades to the PQ scan and host-fetches only the
        # rescore window, then hot scopes get pinned from planner heat
        db.store.set_device_budget(db.store.alive_nbytes() // 3)

        def tiered():
            return db.dsq_batch(queries, paths, k=K, recursive=rec,
                                rescore_k=RESCORE_K)

        acct1 = tiered()[0].batch          # cold: nothing pinned yet
        res2 = tiered()                    # warm: hot scopes pinned
        acct2 = res2[0].batch
        tiered_recall = _recall(fp32_res, res2)
        rows.append({
            "name": f"pq/{ds_name}/tiered",
            "us_per_call": _clock(tiered),
            "derived": (f"recall@{K}={tiered_recall:.4f};"
                        f"fetch_cold_kb={acct1.rescore_fetch_bytes/1e3:.1f};"
                        f"fetch_warm_kb={acct2.rescore_fetch_bytes/1e3:.1f};"
                        f"rows_pinned={acct2.rows_device_pinned};"
                        f"rows_host={acct2.rows_host}"),
        })
        if smoke:
            assert bytes_ratio <= 0.08, (
                f"{ds_name}: PQ codes are {bytes_ratio:.4f}x fp32 (> 0.08)")
            assert recall >= 0.95, (
                f"{ds_name}: PQ recall@{K} {recall:.4f} < 0.95")
            assert acct1.db_bytes_pq > 0, (
                f"{ds_name}: over-budget batch did not auto-upgrade to pq")
            placed = acct2.rows_device_pinned + acct2.rows_host
            assert placed == db.store.alive_count(), (
                f"{ds_name}: tiered placement covers {placed} of "
                f"{db.store.alive_count()} alive rows")
            assert acct1.rescore_fetch_bytes > 0, (
                f"{ds_name}: tiered rescore fetched no host bytes")
            assert acct2.rescore_fetch_bytes < acct1.rescore_fetch_bytes, (
                f"{ds_name}: hot pinning did not reduce the host fetch "
                f"({acct1.rescore_fetch_bytes} -> "
                f"{acct2.rescore_fetch_bytes} bytes)")
            assert tiered_recall >= 0.95, (
                f"{ds_name}: tiered recall@{K} {tiered_recall:.4f} < 0.95")

    # ---- scan wall-clock: PQ ADC vs fp32 flat, gated on all backends ---
    n = max(SCAN_N_FLOOR, int(SCAN_N * scale / SMOKE_SCALE))
    corpus = _scan_corpus(rng, n, SCAN_DIM)
    q = corpus[rng.integers(0, n, SCAN_B)] + 0.3 * rng.normal(
        size=(SCAN_B, SCAN_DIM)).astype(np.float32)
    q = (q / np.linalg.norm(q, axis=1, keepdims=True)).astype(np.float32)
    sdb = DirectoryVectorDB(dim=SCAN_DIM, scope_strategy="triehi",
                            pq_m=SCAN_M)
    sdb.ingest(corpus, ["/corpus"] * n)
    sdb.build_ann("flat")
    spaths = ["/"] * SCAN_B

    def scan_fp32():
        return sdb.dsq_batch(q, spaths, k=K, recursive=True)

    def scan_pq():
        return sdb.dsq_batch(q, spaths, k=K, recursive=True,
                             precision="pq", rescore_k=SCAN_RESCORE_K)

    scan_recall = _recall(scan_fp32(), scan_pq())
    fp32_us, pq_us = _clock(scan_fp32), _clock(scan_pq)
    wallclock = fp32_us / pq_us
    rows.append({
        "name": "pq/scan/fp32_flat",
        "us_per_call": fp32_us,
        "derived": f"n={n};dim={SCAN_DIM};B={SCAN_B};"
                   f"db_mb={sdb.store.alive_nbytes() / 1e6:.2f}",
    })
    rows.append({
        "name": "pq/scan/pq_adc",
        "us_per_call": pq_us,
        "derived": (f"wallclock_speedup={wallclock:.2f}x;"
                    f"bytes_ratio={sdb.store.pq_nbytes() / sdb.store.alive_nbytes():.4f};"
                    f"m={SCAN_M};rescore_k={SCAN_RESCORE_K};"
                    f"recall@{K}={scan_recall:.4f};"
                    f"backend={jax.default_backend()}"),
    })
    if smoke:
        assert wallclock >= 2.0, (
            f"PQ ADC scan only {wallclock:.2f}x the fp32 flat scan on "
            f"{jax.default_backend()} (need >= 2.0 on every backend)")
    return rows


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=SMOKE_SCALE)
    ap.add_argument("--smoke", action="store_true",
                    help="enforce the bytes/recall/tiered/wall-clock gates")
    ap.add_argument("--json", default="",
                    help="also write the result rows to this JSON file")
    args = ap.parse_args()
    from .common import emit
    rows = run(scale=args.scale, smoke=args.smoke)
    emit(rows)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
