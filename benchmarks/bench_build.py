"""Table V: index construction time and size — vector-index baseline vs
baseline + each directory module (the paper reports <1.7% time overhead and
PE-ONLINE < PE-OFFLINE < TRIEHI storage)."""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.vectordb import IVFIndex, VectorStore

from .common import SCALE, DIM, build_index, datasets


def run(scale: float = SCALE) -> List[Dict]:
    rows = []
    for ds_name, ds in datasets(scale).items():
        store = VectorStore(DIM)
        store.add(ds.vectors)
        t0 = time.perf_counter()
        ivf = IVFIndex(store, n_lists=64)
        vec_s = time.perf_counter() - t0
        vec_bytes = store.nbytes() + ivf.nbytes()
        rows.append({"name": f"tableV/{ds_name}/baseline-ivf",
                     "us_per_call": vec_s * 1e6,
                     "derived": f"size_mb={vec_bytes/2**20:.1f}"})
        for strat in ("pe_online", "pe_offline", "triehi"):
            t0 = time.perf_counter()
            idx = build_index(strat, ds)
            dir_s = time.perf_counter() - t0
            dir_bytes = idx.memory_bytes()
            rows.append({
                "name": f"tableV/{ds_name}/{strat}",
                "us_per_call": (vec_s + dir_s) * 1e6,
                "derived": (f"size_mb={(vec_bytes+dir_bytes)/2**20:.1f};"
                            f"dir_mb={dir_bytes/2**20:.2f};"
                            f"overhead_pct={100*dir_s/max(vec_s,1e-9):.1f}"),
            })
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from .common import emit
    emit(run())
