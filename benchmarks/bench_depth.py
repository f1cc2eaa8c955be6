"""Fig. 10/11/12: depth sensitivity.

Fig10: structural complexity by anchor depth (expanded sub-paths m, direct
children c). Fig11: recursive DSQ latency + recall by depth per executor.
Fig12: directory-only latency decomposition (sub-path obtain / bitmap fetch /
bitmap compute / traverse) by depth.
"""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Dict, List

import numpy as np

from repro.core import paths as P
from repro.core.interface import ResolveStats
from repro.datasets import make_wiki_dir
from repro.vectordb import DirectoryVectorDB

from .common import SCALE, DIM, build_index


def run(scale: float = SCALE, max_depth: int = 8, per_depth: int = 24
        ) -> List[Dict]:
    ds = make_wiki_dir(scale=scale, dim=DIM, n_queries=8, seed=0)
    rows: List[Dict] = []
    # anchors grouped by depth, sampled from real entry paths
    rng = np.random.default_rng(0)
    by_depth: Dict[int, List] = defaultdict(list)
    for _ in range(4000):
        p = P.parse(ds.entry_paths[int(rng.integers(ds.n_entries))])
        d = int(rng.integers(1, min(len(p), max_depth) + 1)) if p else 0
        if len(by_depth[d]) < per_depth:
            by_depth[d].append(p[:d])
    indexes = {s: build_index(s, ds)
               for s in ("pe_online", "pe_offline", "triehi")}
    db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi")
    db.ingest(ds.vectors, ds.entry_paths)
    db.build_ann("flat")
    db.build_ann("ivf", n_lists=64)
    has_pg = ds.n_entries <= 12000
    if has_pg:
        db.build_ann("pg", max_degree=12, ef_construction=24)

    for depth in sorted(by_depth):
        anchors = by_depth[depth]
        if not anchors:
            continue
        # ---- Fig 10: structural stats
        m_q = [len(indexes["pe_online"].aux.subtree_keys(a)) for a in anchors]
        c = [len(indexes["pe_online"].aux.children(a)) for a in anchors]
        rows.append({"name": f"fig10/depth{depth}",
                     "us_per_call": 0.0,
                     "derived": (f"anchors={len(anchors)};"
                                 f"m_q={np.mean(m_q):.1f};c={np.mean(c):.1f}")})
        # ---- Fig 12: directory-only decomposition per strategy
        for strat, idx in indexes.items():
            stats = ResolveStats()
            lat = []
            for a in anchors:
                t0 = time.perf_counter_ns()
                idx.resolve(a, recursive=True, stats=stats)
                lat.append((time.perf_counter_ns() - t0) / 1e3)
            stages = ";".join(f"{k}={v/1e3/len(anchors):.1f}us"
                              for k, v in sorted(stats.stage_ns.items()))
            rows.append({"name": f"fig12/depth{depth}/{strat}",
                         "us_per_call": float(np.mean(lat)),
                         "derived": stages})
        # ---- Fig 11: e2e latency by depth for flat + ivf (TrieHI scope)
        q = ds.queries[0]
        executors = [("flat", {}), ("ivf", {"nprobe": 8})]
        if has_pg:
            executors.append(("pg", {"ef_search": 48}))
        for ex_name, params in executors:
            lat = []
            sizes = []
            for a in anchors:
                t0 = time.perf_counter_ns()
                r = db.dsq(q, a, k=10, recursive=True, executor=ex_name,
                           **params)
                lat.append((time.perf_counter_ns() - t0) / 1e3)
                sizes.append(r.scope_size)
            rows.append({"name": f"fig11/depth{depth}/{ex_name}",
                         "us_per_call": float(np.mean(lat)),
                         "derived": f"scope={np.mean(sizes):.0f}"})
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from .common import emit
    emit(run())
