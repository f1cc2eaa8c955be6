"""Sharded vs single-device ``dsq_batch`` over the devices this process sees.

Runs in one process: the mesh spans ``jax.devices()`` (one process per chip —
no child process is started, so nothing competes for an accelerator this
process already holds). On a CPU host, emulate a mesh by starting the process
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; emulated devices
share one CPU, so wall-clock speedup is *reported, never gated*. With fewer
than two devices it fails (a one-shard mesh merges nothing). What this
benchmark measures and (``--smoke``) enforces is the serving-tier contract:

* the same (ids, scores) as the single-device flat batch path — scores within
  a few fp32 ULP (``vectordb.parity``) — before AND immediately after a
  ``dsm_batch`` of move/merge ops;
* per-shard accounting: mask upload happens once (token-validated slots),
  repeated batches hit resident slots, DSM deltas *patch* the shard-resident
  words (patched bytes strictly below one full re-upload of the surviving
  scopes), and the collective term stays O(shards * B * k);
* incremental ingest growth scatters only the new rows (no re-shard).

    [XLA_FLAGS=--xla_force_host_platform_device_count=8] PYTHONPATH=src \
        python -m benchmarks.bench_sharded [--scale S] [--smoke] [--json F]
"""
from __future__ import annotations

import json
from typing import Dict, List

SCALE = 0.01


def run(scale: float = SCALE, smoke: bool = False) -> List[Dict]:
    import time

    import jax
    import numpy as np

    from repro.vectordb import DirectoryVectorDB
    from repro.vectordb.parity import topk_mismatch

    from .common import DIM, datasets

    dev = jax.devices()[0]
    if len(jax.devices()) < 2:
        # a one-shard mesh has no cross-shard merge: every gate below would
        # pass without testing what this benchmark is for
        raise RuntimeError(
            f"bench_sharded needs >= 2 devices, this process sees "
            f"{len(jax.devices())} {dev.platform} device(s); on a CPU host "
            f"start it with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
    where = f"platform={dev.platform};devices={len(jax.devices())}"
    B, K, REPEAT = 64, 10, 3
    rng = np.random.default_rng(0)
    rows: List[Dict] = []
    for ds_name, ds in datasets(scale).items():
        db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi")
        db.ingest(ds.vectors, ds.entry_paths)
        # a contiguous-id subtree for the DSM patch measurement below: its
        # delta occupies a narrow word range, so the word-range scatter is
        # visibly smaller than a full row re-upload. /bench_src/ is sized
        # past the gather threshold (scan plan) and used as a batch anchor,
        # so its packed words are device-resident when the move vacates the
        # fresh subtree from it.
        extra = max(96, int(0.06 * len(db.store)))
        db.ingest(rng.normal(size=(extra, DIM)).astype(np.float32),
                  ["/bench_src/fresh/"] * extra)
        db.build_ann("flat")
        db.build_ann("sharded")
        ex = db.executors["sharded"]
        anchors = (list(dict.fromkeys(ds.query_anchors))[:6]
                   + ["/bench_src/", "/"])
        paths = [anchors[i % len(anchors)] for i in range(B)]
        rec = [True if paths[i] == "/bench_src/" else bool(i % 3)
               for i in range(B)]
        queries = ds.queries[rng.integers(0, len(ds.queries), size=B)] \
            .astype(np.float32)

        def flat_batch():
            return db.dsq_batch(queries, paths, k=K, recursive=rec,
                                executor="flat")

        def sharded_batch():
            return db.dsq_batch(queries, paths, k=K, recursive=rec,
                                executor="sharded")

        def assert_parity():
            for a, b in zip(flat_batch(), sharded_batch()):
                err = topk_mismatch(b.scores, b.ids, a.scores, a.ids)
                assert err is None, err

        # correctness gate: the single-device flat batch's answer
        assert_parity()

        def clock(fn):
            fn()
            t0 = time.perf_counter_ns()
            for _ in range(REPEAT):
                out = fn()
            return (time.perf_counter_ns() - t0) / REPEAT / 1e3, out

        flat_us, _ = clock(flat_batch)
        shard_us, out = clock(sharded_batch)
        acct = out[0].batch
        assert acct.shard_mask_hits == acct.plan_groups.get("scan", 0), \
            "steady-state batches must serve every scan scope from slots"
        rows.append({"name": f"sharded/{ds_name}/flat_batch",
                     "us_per_call": flat_us,
                     "derived": f"B={B};k={K};{where}"})
        rows.append({
            "name": f"sharded/{ds_name}/sharded_batch",
            "us_per_call": shard_us,
            "derived": (f"speedup={flat_us / shard_us:.2f}x;{where};"
                        f"n_shards={acct.n_shards};"
                        f"launches={acct.launches};"
                        f"collective_bytes={acct.collective_bytes};"
                        f"mask_hit_groups={acct.shard_mask_hits}")})

        # DSM: shard-resident masks patch, results stay bit-identical
        m0, up0 = ex.mask_bytes_patched, ex.mask_bytes_uploaded
        db.dsm_batch([("mkdir", "/bench_stage/"),
                      ("move", "/bench_src/fresh/", "/bench_stage/")])
        assert_parity()
        patched_bytes = ex.mask_bytes_patched - m0
        reupload_bytes = ex.mask_bytes_uploaded - up0
        full_row = ex.view.n_words * 4
        rows.append({
            "name": f"sharded/{ds_name}/post_dsm",
            "us_per_call": 0.0,
            "derived": (f"masks_patched={ex.masks_patched};"
                        f"patch_bytes={patched_bytes};"
                        f"full_row_bytes={full_row};"
                        f"reupload_bytes={reupload_bytes}")})

        # incremental ingest: only new rows travel (until capacity)
        b0, r0 = ex.view.db_bytes_uploaded, ex.view.reshards
        grow = min(64, ex.view.cap - len(db.store))
        if grow > 0:
            db.ingest(rng.normal(size=(grow, DIM)).astype(np.float32),
                      ["/"] * grow)
            sharded_batch()
            assert ex.view.reshards == r0
            assert ex.view.db_bytes_uploaded - b0 == grow * DIM * 4
            rows.append({
                "name": f"sharded/{ds_name}/ingest_growth",
                "us_per_call": 0.0,
                "derived": (f"rows={grow};"
                            f"bytes={ex.view.db_bytes_uploaded - b0};"
                            f"reshards=0")})
        if smoke:
            # acceptance gate: the DSM delta really patched (not rebuilt)
            assert ex.masks_patched >= 1, "no shard-resident mask was patched"
            assert patched_bytes > 0
            assert patched_bytes < full_row, (
                "a word-range patch must move less than a full row re-upload")
    return rows


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=SCALE)
    ap.add_argument("--smoke", action="store_true",
                    help="enforce the correctness/accounting acceptance gate")
    ap.add_argument("--json", default="",
                    help="also write the result rows to this JSON file")
    args = ap.parse_args()
    rows = run(scale=args.scale, smoke=args.smoke)
    from .common import emit
    emit(rows)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
