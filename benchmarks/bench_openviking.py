"""§V-F proxy (Tables VI/VII): effect of directory-scoped retrieval on a QA
workload, without external LLMs.

We synthesize a user-memory corpus where each query's relevant evidence lives
inside one directory scope and distractors are semantically similar entries in
other scopes (the paper's /docs vs /archive failure mode). We compare:

  unscoped   : global top-k (a Naive-RAG stand-in)
  scoped     : recursive DSQ at the gold scope, then top-k (OpenViking)

reporting evidence-recall@k and a context token-cost proxy (tokens pulled into
the prompt per question), mirroring the accuracy/token columns of Table VII.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.vectordb import DirectoryVectorDB

from .common import DIM


def _make_memory_corpus(n_users=16, mem_per_user=128, dim=DIM, seed=0):
    rng = np.random.default_rng(seed)
    vecs, paths, gold = [], [], []
    topics = rng.normal(size=(8, dim)).astype(np.float32)
    topics /= np.linalg.norm(topics, axis=1, keepdims=True)
    for u in range(n_users):
        for m in range(mem_per_user):
            t = int(rng.integers(len(topics)))
            v = topics[t] + 0.4 * rng.normal(size=dim).astype(np.float32)
            v /= np.linalg.norm(v)
            vecs.append(v)
            sess = m % 8
            paths.append(f"/users/u{u}/sessions/s{sess}/")
            gold.append((u, t))
    return np.asarray(vecs), paths, gold, topics


def run(n_queries: int = 64, k: int = 5) -> List[Dict]:
    vecs, paths, gold, topics = _make_memory_corpus()
    db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi")
    db.ingest(vecs, paths)
    db.build_ann("flat")
    rng = np.random.default_rng(1)
    rows = []
    for mode in ("unscoped", "scoped"):
        hits, lat, tokens = [], [], []
        for _ in range(n_queries):
            qi = int(rng.integers(len(vecs)))
            u, t = gold[qi]
            q = topics[t] + 0.3 * rng.normal(size=DIM).astype(np.float32)
            q /= np.linalg.norm(q)
            scope = f"/users/u{u}/" if mode == "scoped" else "/"
            t0 = time.perf_counter_ns()
            r = db.dsq(q, scope, k=k, recursive=True)
            lat.append((time.perf_counter_ns() - t0) / 1e3)
            got = [int(i) for i in r.ids[0] if int(i) >= 0]
            # evidence = same user AND same topic
            rel = sum(1 for i in got if gold[i] == (u, t))
            hits.append(rel / k)
            tokens.append(len(got) * 64)      # 64-token chunks proxy
        rows.append({
            "name": f"tableVII/{mode}",
            "us_per_call": float(np.mean(lat)),
            "derived": (f"evidence@{k}={np.mean(hits):.3f};"
                        f"tokens_per_qa={np.mean(tokens):.0f}"),
        })
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from .common import emit
    emit(run())
