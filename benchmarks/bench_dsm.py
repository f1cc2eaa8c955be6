"""Fig. 9 + Table II: DSM latency, write amplification, cache survival.

Four sections:

* ``fig9``  — wall-clock MOVE/MERGE latency on the dataset twins (each
  strategy applies the same generated workload on its own copy).
* ``amp``   — write-amplification accounting (``DSMStats``): structural
  write touches and re-filed posting ids for a MOVE, vs subtree entry count
  at fixed depth and vs depth at fixed size. The Table II shape: TrieHI's
  touches stay O(depth) and re-file nothing, PE-OFFLINE grows with the
  subtree (key remap + per-level re-filing of every entry).
* ``cache`` — cached-mask survival under a mixed DSQ+DSM workload: TrieHI's
  delta events let the planner cache patch surviving masks in place
  (survival ~1.0), the global-epoch PE-* strategies evict everything (0.0).
* ``batch`` — group-committed ``dsm_batch`` vs the looped per-op executor
  (one journal append + FIFO region scheduling for the whole batch).

    PYTHONPATH=src python -m benchmarks.bench_dsm [--scale S] [--smoke]
        [--json out.json]

``--smoke`` runs the scale-free sections only and enforces the acceptance
shape (TrieHI flat vs PE-OFFLINE growth, survival >= 0.5).
"""
from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Dict, List

import numpy as np

from repro.core import (DSM, DSMExecutor, DSMJournal, DSMStats, STRATEGIES,
                        make_scope_index)
from repro.core import paths as P
from repro.vectordb import DirectoryVectorDB

from .common import SCALE, build_index, datasets, pct

AMP_SIZES = (40, 160, 640)       # subtree entry counts, fixed depth
AMP_DEPTHS = (3, 6, 12)          # anchor depth, fixed entry count
CACHE_ROUNDS = 6
SURVIVAL_FLOOR = 0.5             # acceptance: >= 50% under mixed DSQ+DSM


def _subtree_dirs(idx, src: str) -> int:
    """Strategy-agnostic m_u: number of directory keys in the subtree."""
    path = P.parse(src)
    if hasattr(idx, "aux"):
        return len(idx.aux.subtree_keys(path))
    node = idx._walk(path, create=False)
    if node is None:
        raise KeyError(src)
    count, stack = 0, [node]
    while stack:
        n = stack.pop()
        count += 1
        stack.extend(n.children.values())
    return count


# ------------------------------------------------------------------- fig9
def fig9(scale: float = SCALE) -> List[Dict]:
    rows = []
    for ds_name, ds in datasets(scale).items():
        for strat in STRATEGIES:
            for kind, workload in (("move", ds.moves), ("merge", ds.merges)):
                idx = build_index(strat, ds)
                lat, sizes = [], []
                applied = 0
                for src, dst in workload:
                    try:
                        m_u = _subtree_dirs(idx, src)
                        t0 = time.perf_counter_ns()
                        if kind == "move":
                            idx.move(src, dst)
                        else:
                            idx.merge(src, dst)
                        lat.append((time.perf_counter_ns() - t0) / 1e3)
                        sizes.append(m_u)
                        applied += 1
                    except (KeyError, ValueError):
                        continue
                idx.check_invariants()
                p = pct(lat)
                # split into small/large-subtree buckets when m_u is known
                big = [l for l, s_ in zip(lat, sizes) if s_ >= 50]
                small = [l for l, s_ in zip(lat, sizes) if 0 <= s_ < 50]
                extra = ""
                if big and small:
                    extra = (f";small_mu_us={np.mean(small):.1f}"
                             f";large_mu_us={np.mean(big):.1f}")
                rows.append({
                    "name": f"fig9/{ds_name}/{kind}/{strat}",
                    "us_per_call": p["mean"],
                    "derived": (f"applied={applied};p95={p['p95']:.1f};"
                                f"p99={p['p99']:.1f}" + extra),
                })
    return rows


# ------------------------------------------------------ write amplification
def _bulk_subtree(idx, n_entries: int, top: str, eid_base: int = 0) -> None:
    """n_entries spread over ~n_entries//8 leaf dirs under ``top``."""
    for i in range(n_entries):
        idx.insert(eid_base + i, f"{top}g{i % max(1, n_entries // 8)}/")


def amp() -> List[Dict]:
    rows = []
    for strat in STRATEGIES:
        for n in AMP_SIZES:
            idx = make_scope_index(strat)
            idx.insert(10 ** 6, "/dst/keep/")
            _bulk_subtree(idx, n, "/a/b/big/")
            stats = DSMStats()
            t0 = time.perf_counter_ns()
            idx.move("/a/b/big/", "/dst/", stats=stats)
            us = (time.perf_counter_ns() - t0) / 1e3
            rows.append({
                "name": f"amp/move_size{n}/{strat}",
                "us_per_call": us,
                "derived": (f"write_touches={stats.write_touches};"
                            f"ids_rewritten={stats.ids_rewritten};"
                            f"agg_bits={stats.agg_bits_updated};"
                            f"keys_rekeyed={stats.keys_rekeyed}"),
            })
        for d in AMP_DEPTHS:
            idx = make_scope_index(strat)
            chain = "/" + "/".join(f"c{i}" for i in range(d)) + "/"
            for eid in range(64):
                idx.insert(eid, chain)
            idx.mkdir("/dst/")
            stats = DSMStats()
            t0 = time.perf_counter_ns()
            idx.move(chain, "/dst/", stats=stats)
            us = (time.perf_counter_ns() - t0) / 1e3
            rows.append({
                "name": f"amp/move_depth{d}/{strat}",
                "us_per_call": us,
                "derived": (f"write_touches={stats.write_touches};"
                            f"ids_rewritten={stats.ids_rewritten};"
                            f"agg_bits={stats.agg_bits_updated}"),
            })
    return rows


# ---------------------------------------------------------- cache survival
def cache_survival() -> List[Dict]:
    """Mixed DSQ+DSM serving loop: hot scopes stay resident across rounds
    only if the DSM deltas patch them; survival = fraction of cached masks
    still token-valid immediately after each DSM."""
    rows = []
    n_top = CACHE_ROUNDS + 2
    for strat in ("triehi", "pe_offline", "pe_online"):
        rng = np.random.default_rng(0)
        paths = []
        for t in range(n_top):
            for j in range(24):
                paths.append(f"/t{t}/" if j % 2 else f"/t{t}/in{t}/")
        vecs = rng.normal(size=(len(paths), 16)).astype(np.float32)
        db = DirectoryVectorDB(dim=16, scope_strategy=strat)
        db.ingest(vecs, paths)
        db.build_ann("flat")
        queries = rng.normal(size=(16, 16)).astype(np.float32)
        scopes = ["/"] * 4 + [f"/t{t}/" for t in range(n_top)]
        scopes += ["/"] * (16 - len(scopes))
        idx = db.namespaces["fs"]
        cache = db.planner().cache
        survivals, dsq_us, dsm_us = [], [], []
        for r in range(CACHE_ROUNDS):
            t0 = time.perf_counter_ns()
            db.dsq_batch(queries, scopes, k=5)
            t1 = time.perf_counter_ns()
            db.move(f"/t{r}/in{r}/", f"/t{r + 1}/")
            t2 = time.perf_counter_ns()
            valid, total = cache.revalidate(idx, len(db.store))
            survivals.append(valid / max(1, total))
            dsq_us.append((t1 - t0) / 1e3)
            dsm_us.append((t2 - t1) / 1e3)
        # correctness spot check after the churn
        want = db.dsq(queries[0], "/", k=5)
        got = db.dsq_batch(queries[:1], ["/"], k=5)[0]
        np.testing.assert_array_equal(got.ids, want.ids)
        db.check_invariants()
        cs = cache.stats()
        rows.append({
            "name": f"cache/mixed_dsq_dsm/{strat}",
            "us_per_call": float(np.mean(dsq_us)),
            "derived": (f"survival={np.mean(survivals):.2f};"
                        f"dsm_us={np.mean(dsm_us):.1f};"
                        f"patched={cs['patched']};"
                        f"invalidations={cs['invalidations']};"
                        f"hit_rate="
                        f"{cs['hits'] / max(1, cs['hits'] + cs['misses']):.2f}"),
            "survival": float(np.mean(survivals)),
        })
    return rows


# ------------------------------------------------------------ batched DSM
def batch_vs_loop() -> List[Dict]:
    rows = []
    n_top = 16

    def seed(idx):
        for eid in range(n_top * 8):
            idx.insert(eid, f"/t{eid % n_top}/d{(eid // n_top) % 4}/")

    def ops_for(round_: int) -> List[DSM]:
        out = []
        for t in range(n_top):
            out.append(DSM("move", f"/t{t}/d{round_ % 4}/",
                           f"/t{t}/sub{round_}/"))
        return out

    with tempfile.TemporaryDirectory() as tmp:
        loop_idx = make_scope_index("triehi")
        batch_idx = make_scope_index("triehi")
        seed(loop_idx)
        seed(batch_idx)
        loop_ex = DSMExecutor(loop_idx,
                              DSMJournal(os.path.join(tmp, "loop.journal")))
        batch_ex = DSMExecutor(batch_idx,
                               DSMJournal(os.path.join(tmp, "batch.journal")))
        loop_ns = batch_ns = 0
        applied = 0
        for r in range(3):
            ops = ops_for(r)
            t0 = time.perf_counter_ns()
            for op in ops:
                loop_ex.apply(op)
            t1 = time.perf_counter_ns()
            res = batch_ex.apply_many(ops, max_workers=4)
            t2 = time.perf_counter_ns()
            loop_ns += t1 - t0
            batch_ns += t2 - t1
            applied += res.applied
            assert all(e is None for e in res.errors)
        for probe in ["/", "/t0/", "/t5/sub1/"]:
            assert (set(loop_idx.resolve(probe).to_array().tolist())
                    == set(batch_idx.resolve(probe).to_array().tolist()))
        batch_idx.check_invariants()
    n_ops = 3 * n_top
    rows.append({"name": "batch/looped_apply/triehi",
                 "us_per_call": loop_ns / n_ops / 1e3,
                 "derived": f"ops={n_ops};journal_appends={2 * n_ops}"})
    rows.append({"name": "batch/apply_many/triehi",
                 "us_per_call": batch_ns / n_ops / 1e3,
                 "derived": (f"ops={n_ops};journal_appends={2 * 3};"
                             f"speedup={loop_ns / max(1, batch_ns):.2f}x")})
    return rows


# ---------------------------------------------------------------- harness
def check_acceptance(rows: List[Dict]) -> None:
    """The Table II shape + survival floor (CI smoke gate)."""
    by_name = {r["name"]: r for r in rows}

    def derived(name: str, key: str) -> float:
        fields = dict(kv.split("=") for kv in by_name[name]["derived"]
                      .split(";") if "=" in kv)
        return float(fields[key].rstrip("x"))

    lo, hi = AMP_SIZES[0], AMP_SIZES[-1]
    tri_lo = derived(f"amp/move_size{lo}/triehi", "write_touches")
    tri_hi = derived(f"amp/move_size{hi}/triehi", "write_touches")
    assert tri_hi <= tri_lo, \
        f"TrieHI structural writes grew with subtree size ({tri_lo}->{tri_hi})"
    assert derived(f"amp/move_size{hi}/triehi", "ids_rewritten") == 0
    peo_lo = derived(f"amp/move_size{lo}/pe_offline", "write_touches")
    peo_hi = derived(f"amp/move_size{hi}/pe_offline", "write_touches")
    assert peo_hi >= 4 * peo_lo, \
        f"PE-OFFLINE writes must grow with subtree size ({peo_lo}->{peo_hi})"
    assert (derived(f"amp/move_size{hi}/pe_offline", "ids_rewritten")
            >= 4 * derived(f"amp/move_size{lo}/pe_offline", "ids_rewritten"))
    d_lo, d_hi = AMP_DEPTHS[0], AMP_DEPTHS[-1]
    assert (derived(f"amp/move_depth{d_hi}/triehi", "write_touches")
            >= derived(f"amp/move_depth{d_lo}/triehi", "write_touches")
            + (d_hi - d_lo) - 1), "TrieHI touches must grow O(depth)"

    tri_surv = by_name["cache/mixed_dsq_dsm/triehi"]["survival"]
    peo_surv = by_name["cache/mixed_dsq_dsm/pe_offline"]["survival"]
    assert tri_surv >= SURVIVAL_FLOOR, \
        f"TrieHI cached-mask survival {tri_surv:.2f} < {SURVIVAL_FLOOR}"
    assert peo_surv <= 0.05, f"PE-OFFLINE survival unexpectedly {peo_surv}"


def run(scale: float = SCALE, smoke: bool = False) -> List[Dict]:
    rows: List[Dict] = []
    if not smoke:
        rows += fig9(scale)
    rows += amp()
    rows += cache_survival()
    rows += batch_vs_loop()
    if smoke:
        check_acceptance(rows)
    return rows


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=SCALE)
    ap.add_argument("--smoke", action="store_true",
                    help="scale-free sections only, acceptance-shape "
                         "assertions enforced (CI gate)")
    ap.add_argument("--json", default="",
                    help="also write the result rows to this JSON file")
    args = ap.parse_args()
    from .common import emit
    rows = run(scale=args.scale, smoke=args.smoke)
    emit(rows)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
    if args.smoke:
        print("# dsm smoke: acceptance shape OK (Table II contrast + "
              f"survival >= {SURVIVAL_FLOOR})")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
