"""Calibrated planner vs hand-set heuristics on the serving-shaped workload.

Two identical databases over the same dataset: one pinned to the heuristic
constants (``calibration=False``), one reading the committed calibration
artifact. The acceptance contract of the measured decision layer:

* **never slower** — the calibrated planner must not regress any workload
  (its decisions are measured on this backend; ties are fine);
* **deterministic** — a fixed artifact yields bit-identical plans and results
  across independent database instances (the single-decision-rule contract);
* **recall only improves** — every measured flip is clamped toward exactness
  (int8 -> fp32 upgrades, rescore floors), so calibrated recall against the
  exact oracle can never drop below the heuristic's.

    PYTHONPATH=src python -m benchmarks.bench_autotune           # full scale
    PYTHONPATH=src python -m benchmarks.bench_autotune --smoke   # CI gate

The strict assertions only arm when the artifact is *measured* for the
running backend (a roofline fallback has no never-slower promise).
"""
from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

import numpy as np

from repro.vectordb import DirectoryVectorDB
from repro.vectordb.costmodel import ENV_CALIBRATION, resolve_calibration

from .common import DIM, SCALE, datasets

B = 64          # concurrent requests per batch
K = 10
N_UNIQUE = 8    # distinct scopes in the mix
REPEAT = 5      # timed batches per path (after one warmup)
TOLERANCE = 1.2  # never-slower gate, with headroom for timer noise

DEFAULT_ARTIFACT = os.path.join(os.path.dirname(__file__), "..",
                                "calibration", "cpu.json")


def _requests(ds, rng):
    anchors = list(dict.fromkeys(ds.query_anchors))[:N_UNIQUE - 1] + ["/"]
    paths = [anchors[i % len(anchors)] for i in range(B)]
    rec = [bool(i % 3) for i in range(B)]
    queries = ds.queries[rng.integers(0, len(ds.queries), size=B)]
    return queries.astype(np.float32), paths, rec


def _recall(results, oracle) -> float:
    hits, total = 0, 0
    for r, o in zip(results, oracle):
        want = set(int(i) for i in o.ids[0] if i >= 0)
        if not want:
            continue
        hits += len(set(int(i) for i in r.ids[0] if i >= 0) & want)
        total += len(want)
    return hits / max(total, 1)


def _clock(fn):
    fn()                                       # warmup (jit, cache fill)
    t0 = time.perf_counter_ns()
    for _ in range(REPEAT):
        out = fn()
    return (time.perf_counter_ns() - t0) / REPEAT / 1e3, out


def _fingerprint(results) -> tuple:
    """Hashable plan+result identity of a batch (the determinism gate)."""
    return tuple((r.plan, r.scope_size, r.ids.tobytes(), r.scores.tobytes())
                 for r in results)


def run(scale: float = SCALE, strict: bool = False,
        artifact: Optional[str] = None) -> List[Dict]:
    artifact = (artifact or os.environ.get(ENV_CALIBRATION)
                or DEFAULT_ARTIFACT)
    model = resolve_calibration(artifact)
    measured = model.source == "measured"
    rng = np.random.default_rng(0)
    rows: List[Dict] = []
    wins = 0
    for ds_name, ds in datasets(scale).items():
        dbs = {}
        for tag, cal in (("heuristic", False), ("calibrated", model)):
            db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi",
                                   calibration=cal)
            db.ingest(ds.vectors, ds.entry_paths)
            db.build_ann("flat")
            dbs[tag] = db
        queries, paths, rec = _requests(ds, rng)
        # exact oracle: the heuristic fp32 path is bit-exact by construction
        oracle = dbs["heuristic"].dsq_batch(queries, paths, k=K,
                                            recursive=rec)
        for precision in ("fp32", "int8"):
            timing, recall, res = {}, {}, {}
            for tag in ("heuristic", "calibrated"):
                timing[tag], out = _clock(
                    lambda t=tag: dbs[t].dsq_batch(
                        queries, paths, k=K, recursive=rec,
                        precision=precision))
                recall[tag] = _recall(out, oracle)
                res[tag] = out
            speedup = timing["heuristic"] / timing["calibrated"]
            if speedup > 1.0:
                wins += 1
            acct = res["calibrated"][0].batch
            for tag in ("heuristic", "calibrated"):
                a = res[tag][0].batch
                rows.append({
                    "name": f"autotune/{ds_name}/{precision}/{tag}",
                    "us_per_call": timing[tag],
                    "derived": (f"recall={recall[tag]:.4f};"
                                f"plan_source={a.plan_source or 'heuristic'};"
                                f"plans={a.plan_groups}"
                                + (f";speedup={speedup:.2f}x;"
                                   f"predicted_us="
                                   f"{a.predicted_ann_ns / 1e3:.0f}"
                                   if tag == "calibrated" else "")),
                })
            if not strict:
                continue
            # determinism: a fresh database under the same artifact must
            # produce bit-identical plans AND results
            db2 = DirectoryVectorDB(dim=DIM, scope_strategy="triehi",
                                    calibration=model)
            db2.ingest(ds.vectors, ds.entry_paths)
            db2.build_ann("flat")
            again = db2.dsq_batch(queries, paths, k=K, recursive=rec,
                                  precision=precision)
            assert _fingerprint(again) == _fingerprint(res["calibrated"]), (
                f"{ds_name}/{precision}: calibrated plans not deterministic "
                f"under a fixed artifact")
            if measured:
                assert acct.plan_source == "measured", acct.plan_source
                assert timing["calibrated"] <= timing["heuristic"] * \
                    TOLERANCE, (
                    f"{ds_name}/{precision}: calibrated "
                    f"{timing['calibrated']:.0f}us slower than heuristic "
                    f"{timing['heuristic']:.0f}us")
                assert recall["calibrated"] >= recall["heuristic"] - 1e-9, (
                    f"{ds_name}/{precision}: calibrated recall "
                    f"{recall['calibrated']:.4f} below heuristic "
                    f"{recall['heuristic']:.4f}")
    if strict and measured:
        assert wins >= 1, "calibrated planner won no workload"
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    import argparse

    from .common import emit
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced scale, strict gates (the CI entry point)")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--artifact", default=None,
                    help=f"calibration artifact (default $"
                         f"{ENV_CALIBRATION} or calibration/cpu.json)")
    args = ap.parse_args()
    scale = args.scale if args.scale is not None else (
        0.002 if args.smoke else SCALE)
    emit(run(scale, strict=True, artifact=args.artifact))
