"""Table IV: directory-only latency (µs) for candidate entry-ID set
generation — recursive + non-recursive × {PE-ONLINE, PE-OFFLINE, TRIEHI} ×
{WIKI-Dir, ARXIV-Dir twins}, with mean/P90/P95/P99/P99.9."""
from __future__ import annotations

import time
from typing import Dict, List

from .common import SCALE, build_index, datasets, pct


def run(scale: float = SCALE) -> List[Dict]:
    rows = []
    for ds_name, ds in datasets(scale).items():
        indexes = {s: build_index(s, ds) for s in
                   ("pe_online", "pe_offline", "triehi")}
        # beyond-paper: wildcard DSQ (§IV-A derived patterns) — TrieHI answers
        # by branch-pruned traversal, expansion designs must key-scan
        wild = [("/*/",), ("*", "*"), ds.dirs[len(ds.dirs) // 2][:1] + ("*",)]
        for strat, idx in indexes.items():
            lat = []
            for pat in wild:
                t0 = time.perf_counter_ns()
                idx.resolve_pattern(pat)
                lat.append((time.perf_counter_ns() - t0) / 1e3)
            rows.append({
                "name": f"wildcard/{ds_name}/{strat}",
                "us_per_call": sum(lat) / len(lat),
                "derived": f"patterns={len(wild)}",
            })
        for recursive in (True, False):
            for strat, idx in indexes.items():
                lat = []
                for anchor in ds.query_anchors:
                    t0 = time.perf_counter_ns()
                    idx.resolve(anchor, recursive=recursive)
                    lat.append((time.perf_counter_ns() - t0) / 1e3)
                p = pct(lat)
                rows.append({
                    "name": f"tableIV/{ds_name}/"
                            f"{'recur' if recursive else 'nonrecur'}/{strat}",
                    "us_per_call": p["mean"],
                    "derived": (f"p90={p['p90']:.1f};p95={p['p95']:.1f};"
                                f"p99={p['p99']:.1f};p999={p['p999']:.1f}"),
                })
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from .common import emit
    emit(run())
