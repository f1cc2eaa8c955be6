"""Int8 scalar-quantized tier vs the fp32 exact scan.

The same serving-shaped workload as ``bench_dsq_batch`` (a 64-request
mixed-scope batch over a handful of hot scopes), ranked twice through
``dsq_batch(executor="flat")``: once at the default fp32 precision and once
at ``precision="int8"`` (quantized scan selects ``rescore_k`` candidates,
exact fp32 gather-rescore ranks the final top-k).

Reported per dataset twin, gated with ``--smoke``:

* ``bytes_ratio``  — int8 device-store bytes / fp32 bytes, measured from the
  store accounting. Gate: <= 0.30.
* ``recall@10``    — int8 (default rescore window) against the fp32 exact
  top-k. Gate: >= 0.99 on both twins.
* ``scan_speedup`` — the scan-phase term, two forms:
  - ``roofline``: fp32 scan HBM bytes / (int8 scan bytes + fp32 rescore
    gather bytes) per batch — the bandwidth term the quantized tier is
    built around (`EXPERIMENTS.md §Int8 roofline`). Gate: >= 2.0.
  - ``wallclock``: measured batch-latency ratio. Gated >= 2.0 only on
    accelerator backends (tpu/gpu): XLA:CPU lowers the int8 dot to a
    scalar int32 loop (no VNNI path), so on CPU containers the honest
    wall-clock is reported but not enforced — the same policy as
    ``bench_ivf_batch --no-strict`` and ``bench_roofline``'s derived terms.

    PYTHONPATH=src python -m benchmarks.bench_quantized [--scale S] \
        [--smoke] [--json out.json]
"""
from __future__ import annotations

import json
import time
from typing import Dict, List

import numpy as np

from repro.vectordb import DirectoryVectorDB

from .common import DIM, datasets

B = 64          # concurrent requests per batch
K = 10
N_UNIQUE = 8    # distinct scopes in the mix
REPEAT = 3      # timed batches per path (after one warmup)
SMOKE_SCALE = 0.01   # floor for --smoke: the scan term needs n >> B*rescore


def _requests(ds, rng):
    anchors = list(dict.fromkeys(ds.query_anchors))[:N_UNIQUE - 1] + ["/"]
    paths = [anchors[i % len(anchors)] for i in range(B)]
    rec = [bool(i % 3) for i in range(B)]
    queries = ds.queries[rng.integers(0, len(ds.queries), size=B)]
    return queries.astype(np.float32), paths, rec


def _recall(fp32_res, int8_res) -> float:
    hits = total = 0
    for a, b in zip(fp32_res, int8_res):
        want = set(int(x) for x in a.ids[0] if int(x) >= 0)
        got = set(int(x) for x in b.ids[0] if int(x) >= 0)
        hits += len(want & got)
        total += len(want)
    return hits / max(total, 1)


def run(scale: float = SMOKE_SCALE, smoke: bool = False) -> List[Dict]:
    import jax
    if smoke:
        scale = max(scale, SMOKE_SCALE)
    accel = jax.default_backend() in ("tpu", "gpu")
    rng = np.random.default_rng(0)
    rows = []
    for ds_name, ds in datasets(scale).items():
        db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi")
        db.ingest(ds.vectors, ds.entry_paths)
        db.build_ann("flat")
        queries, paths, rec = _requests(ds, rng)

        def fp32():
            return db.dsq_batch(queries, paths, k=K, recursive=rec)

        def int8():
            return db.dsq_batch(queries, paths, k=K, recursive=rec,
                                precision="int8")

        # correctness + recall gate before timing anything
        fp32_res, int8_res = fp32(), int8()
        recall = _recall(fp32_res, int8_res)
        n = len(db.store)
        bytes_ratio = db.store.q_nbytes() / db.store.nbytes()
        acct = int8_res[0].batch
        # bandwidth-roofline scan term: what each batch streams from the
        # device store. fp32 scan reads the full fp32 store once per shared
        # launch; the int8 path reads the quantized store plus the fp32
        # rows of the rescored candidates.
        fp32_scan_bytes = db.store.nbytes()
        int8_scan_bytes = (db.store.q_nbytes()
                           + acct.rescore_candidates * DIM * 4)
        roofline = fp32_scan_bytes / int8_scan_bytes

        def clock(fn):
            fn()                                  # warmup (jit, cache fill)
            t0 = time.perf_counter_ns()
            for _ in range(REPEAT):
                fn()
            return (time.perf_counter_ns() - t0) / REPEAT / 1e3

        fp32_us = clock(fp32)
        int8_us = clock(int8)
        wallclock = fp32_us / int8_us
        rows.append({
            "name": f"quantized/{ds_name}/fp32",
            "us_per_call": fp32_us,
            "derived": f"n={n};db_mb={db.store.nbytes() / 1e6:.2f}",
        })
        rows.append({
            "name": f"quantized/{ds_name}/int8",
            "us_per_call": int8_us,
            "derived": (f"bytes_ratio={bytes_ratio:.3f};"
                        f"recall@{K}={recall:.4f};"
                        f"roofline_speedup={roofline:.2f}x;"
                        f"wallclock_speedup={wallclock:.2f}x;"
                        f"rescored={acct.rescore_candidates};"
                        f"backend={jax.default_backend()}"),
        })
        if smoke:
            assert bytes_ratio <= 0.30, (
                f"{ds_name}: int8 store is {bytes_ratio:.3f}x fp32 (> 0.30)")
            assert recall >= 0.99, (
                f"{ds_name}: int8 recall@{K} {recall:.4f} < 0.99")
            assert roofline >= 2.0, (
                f"{ds_name}: scan roofline term only {roofline:.2f}x")
            if accel:
                assert wallclock >= 2.0, (
                    f"{ds_name}: int8 scan only {wallclock:.2f}x on "
                    f"{jax.default_backend()}")
    return rows


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=SMOKE_SCALE)
    ap.add_argument("--smoke", action="store_true",
                    help="enforce the bytes/recall/scan-term gates")
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
