"""Benchmark driver: one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--scale 0.01] [--only dsq,...]
[--json out.json]`` prints ``name,us_per_call,derived`` CSV rows for every
benchmark; ``--json`` additionally dumps ``{section: rows}`` to a file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.01,
                    help="fraction of published dataset sizes")
    ap.add_argument("--only", default="",
                    help="comma list: dsq,dsq_batch,ivf_batch,sharded,"
                         "quantized,pq,serve,autotune,maintenance,faults,"
                         "e2e,dsm,build,depth,openviking,roofline,kernels")
    ap.add_argument("--json", default="",
                    help="also write {section: rows} to this JSON file")
    args = ap.parse_args()
    only = set(filter(None, args.only.split(",")))

    from . import (bench_autotune, bench_build, bench_depth, bench_dsm,
                   bench_dsq_batch, bench_dsq_e2e, bench_dsq_latency,
                   bench_faults, bench_ivf_batch, bench_kernels,
                   bench_maintenance, bench_openviking, bench_pq,
                   bench_quantized, bench_roofline, bench_serve,
                   bench_sharded)
    from .common import emit

    sections = [
        ("dsq", "Table IV: directory-only latency",
         lambda: bench_dsq_latency.run(args.scale)),
        ("dsq_batch", "Batched multi-scope DSQ vs per-request loop",
         lambda: bench_dsq_batch.run(args.scale)),
        ("ivf_batch", "Batched device-resident IVF DSQ vs per-request loop",
         lambda: bench_ivf_batch.run(args.scale)),
        ("sharded", "Sharded vs single-device dsq_batch (visible devices)",
         lambda: bench_sharded.run(args.scale)),
        ("quantized", "Int8 scalar-quantized tier vs fp32 exact scan",
         lambda: bench_quantized.run(args.scale)),
        ("pq", "PQ/ADC tier + tiered fp32 host storage vs fp32 flat scan",
         lambda: bench_pq.run(args.scale)),
        ("serve", "Continuous-batching serving vs sync batch=1 loop",
         lambda: bench_serve.run(args.scale)),
        ("autotune", "Calibrated planner vs hand-set heuristics",
         lambda: bench_autotune.run(args.scale)),
        ("maintenance", "Online maintenance under streaming churn",
         lambda: bench_maintenance.run(args.scale)),
        ("faults", "Chaos: degraded-mode serving + crash recovery",
         lambda: bench_faults.run(args.scale)),
        ("e2e", "Fig 7/8: DSQ quality vs latency",
         lambda: bench_dsq_e2e.run(args.scale)),
        ("dsm", "Fig 9: DSM MOVE/MERGE latency",
         lambda: bench_dsm.run(args.scale)),
        ("build", "Table V: index build time/size",
         lambda: bench_build.run(args.scale)),
        ("depth", "Fig 10-12: depth sensitivity + decomposition",
         lambda: bench_depth.run(args.scale)),
        ("openviking", "Table VI/VII proxy: scoped vs unscoped QA retrieval",
         lambda: bench_openviking.run()),
        ("roofline", "§Roofline: dry-run derived terms (16x16 baseline)",
         lambda: bench_roofline.run()),
        ("kernels", "Pallas kernel microbench (interpret mode)",
         lambda: bench_kernels.run()),
    ]
    collected = {}
    print("name,us_per_call,derived")
    for key, title, fn in sections:
        if only and key not in only:
            continue
        print(f"# --- {title}", flush=True)
        t0 = time.time()
        try:
            rows = fn()
            emit(rows)
            collected[key] = rows
        except Exception as e:  # keep the harness going; report the failure
            print(f"{key},nan,ERROR:{type(e).__name__}:{e}", file=sys.stdout)
        print(f"# --- {title} done in {time.time()-t0:.1f}s", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(collected, f, indent=2)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
