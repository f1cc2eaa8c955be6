"""Fig. 7/8: end-to-end DSQ quality vs latency — recursive + non-recursive,
three strategies × {flat, IVF, PG} executors. Recall@10 against brute-force
ground truth inside the resolved scope."""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.datasets import brute_force_ground_truth
from repro.vectordb import DirectoryVectorDB

from .common import SCALE, DIM, datasets


def run(scale: float = SCALE, pg_cap: int = 4000) -> List[Dict]:
    rows = []
    for ds_name, ds in datasets(scale).items():
        gt = brute_force_ground_truth(ds, k=10)
        for strat in ("pe_online", "pe_offline", "triehi"):
            db = DirectoryVectorDB(dim=DIM, scope_strategy=strat)
            db.ingest(ds.vectors, ds.entry_paths)
            db.build_ann("flat")
            db.build_ann("ivf", n_lists=64)
            executors = [("flat", {})]
            for nprobe in (4, 16, 32):
                executors.append((f"ivf@{nprobe}", {"nprobe": nprobe}))
            if ds.n_entries <= pg_cap:
                db.build_ann("pg", max_degree=12, ef_construction=32)
                for ef in (32, 128):
                    executors.append((f"pg@{ef}", {"ef_search": ef}))
            for ex_name, params in executors:
                lat, recall = [], []
                base = ex_name.split("@")[0]
                for qi in range(len(ds.queries)):
                    t0 = time.perf_counter_ns()
                    r = db.dsq(ds.queries[qi], ds.query_anchors[qi], k=10,
                               recursive=bool(ds.query_recursive[qi]),
                               executor=base, **params)
                    lat.append((time.perf_counter_ns() - t0) / 1e3)
                    want = set(gt[qi][gt[qi] >= 0].tolist())
                    if want:
                        got = set(r.ids[0][r.ids[0] >= 0].tolist())
                        recall.append(len(got & want) / len(want))
                rows.append({
                    "name": f"fig7-8/{ds_name}/{strat}/{ex_name}",
                    "us_per_call": float(np.mean(lat)),
                    "derived": f"recall@10={np.mean(recall):.4f}",
                })
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from .common import emit
    emit(run())
