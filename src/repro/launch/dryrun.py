import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import: jax locks the device
# count at first init, and the production dry-run needs 512 host devices.

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import numpy as np       # noqa: E402

from repro.analysis import roofline as RL                     # noqa: E402
from repro.configs import SHAPES, ARCHS, cell_applicable, get_arch  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_device_count  # noqa: E402
from repro.launch.specs import make_step_fn                   # noqa: E402

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def compile_cell(arch: str, shape_name: str, multi_pod: bool,
                 n_layers_override=None, tag: str = "full",
                 arch_overrides=None):
    """Lower + compile one (arch × shape × mesh) cell; returns metrics dict."""
    cfg = get_arch(arch)
    if arch_overrides:
        cfg = cfg.replace(**arch_overrides)
    if n_layers_override is not None:
        # unrolled + loop-free attention so cost_analysis sees every FLOP
        enc = (dict(encoder_layers=n_layers_override) if cfg.is_encdec else {})
        cfg = cfg.replace(n_layers=n_layers_override, scan_layers=False,
                          attn_impl="naive", **enc)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_device_count(mesh)
    fn, args, shardings, donate = make_step_fn(cfg, shape, mesh)
    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn, in_shardings=shardings,
                          donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
    compile_s = time.time() - t0
    metrics = RL.cost_summary(compiled)
    metrics["compile_s"] = compile_s
    metrics["chips"] = chips
    metrics["tag"] = tag
    # per-device -> global compute/memory totals
    metrics["flops_global"] = metrics["flops"] * chips
    metrics["bytes_global"] = metrics["bytes"] * chips
    print(compiled.memory_analysis())
    cost = compiled.cost_analysis() or {}
    print({k: cost[k] for k in ("flops", "bytes accessed") if k in cost})
    del compiled, lowered
    return metrics


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             with_roofline: bool = True, arch_overrides=None,
             tag_suffix: str = "") -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "n_layers": cfg.n_layers, "skipped": not ok, "reason": reason,
           "params": cfg.param_count(),
           "active_params": cfg.active_param_count(),
           "model_flops": RL.model_flops_estimate(cfg, shape)}
    if not ok:
        return rec
    try:
        rec["full"] = compile_cell(arch, shape_name, multi_pod, tag="full",
                                   arch_overrides=arch_overrides)
        if with_roofline:
            l1 = compile_cell(arch, shape_name, multi_pod, 1, "L1",
                              arch_overrides)
            l2 = compile_cell(arch, shape_name, multi_pod, 2, "L2",
                              arch_overrides)
            rec["L1"], rec["L2"] = l1, l2
            rec["extrapolated"] = RL.extrapolate(
                l1, l2, cfg.n_layers,
                keys=("flops", "bytes", "link_bytes", "flops_global",
                      "bytes_global"))
        rec["ok"] = True
    except Exception as e:  # record failures as bugs-to-fix, keep sweeping
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def run_viking_scan(multi_pod: bool, n_total: int = 2 ** 28, dim: int = 1024,
                    n_queries: int = 64, k: int = 100,
                    dtype: str = "bfloat16") -> dict:
    """Dry-run of the paper-technique serving step: directory-scoped top-k
    over the pod-sharded vector store (DSQ after TrieHI scope resolution)."""
    import jax.numpy as jnp
    from repro.distributed.search import make_scoped_search, search_input_specs
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_device_count(mesh)
    rec = {"arch": "viking-scan", "shape": f"n{n_total}_q{n_queries}_k{k}_{dtype}",
           "mesh": "2x16x16" if multi_pod else "16x16",
           "model_flops": 2.0 * n_total * dim * n_queries}
    try:
        t0 = time.time()
        jdt = {"bfloat16": jnp.bfloat16, "int8": jnp.int8}[dtype]
        fn = make_scoped_search(mesh, n_total, dim, k, dtype=jdt)
        args, shardings = search_input_specs(mesh, n_total, dim, n_queries,
                                             dtype=jdt)
        with mesh:
            import functools
            lowered = jax.jit(fn.__wrapped__ if hasattr(fn, "__wrapped__")
                              else fn, in_shardings=shardings).lower(*args)
            compiled = lowered.compile()
        m = RL.cost_summary(compiled)
        m["compile_s"] = time.time() - t0
        m["chips"] = chips
        m["flops_global"] = m["flops"] * chips
        m["bytes_global"] = m["bytes"] * chips
        print(compiled.memory_analysis())
        rec["full"] = m
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def run_viking_scan_batch(multi_pod: bool, n_total: int = 2 ** 28,
                          dim: int = 1024, n_queries: int = 64,
                          n_scopes: int = 16, k: int = 100) -> dict:
    """Dry-run of the batched sharded serving step: one shard_map launch
    ranks a heterogeneous mixed-scope request batch against the
    device-resident packed scope-mask table (the ``ShardedExecutor`` launch,
    ``distributed.search.make_sharded_batch_search``)."""
    from repro.distributed.search import (make_sharded_batch_search,
                                          multi_scope_search_input_specs)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_device_count(mesh)
    rec = {"arch": "viking-scan-batch",
           "shape": f"n{n_total}_q{n_queries}_s{n_scopes}_k{k}",
           "mesh": "2x16x16" if multi_pod else "16x16",
           "model_flops": 2.0 * n_total * dim * n_queries}
    try:
        t0 = time.time()
        fn = make_sharded_batch_search(mesh, n_total, dim, k)
        args, shardings = multi_scope_search_input_specs(
            mesh, n_total, dim, n_queries, n_scopes)
        with mesh:
            lowered = jax.jit(fn.__wrapped__ if hasattr(fn, "__wrapped__")
                              else fn, in_shardings=shardings).lower(*args)
            compiled = lowered.compile()
        m = RL.cost_summary(compiled)
        m["compile_s"] = time.time() - t0
        m["chips"] = chips
        m["flops_global"] = m["flops"] * chips
        m["bytes_global"] = m["bytes"] * chips
        print(compiled.memory_analysis())
        rec["full"] = m
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, help="single arch (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES),
                    help="single shape (default: all)")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--no-roofline", action="store_true",
                    help="skip L1/L2 extrapolation compiles")
    ap.add_argument("--viking-scan", action="store_true",
                    help="also dry-run the scoped-search serving step")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    ap.add_argument("--force", action="store_true", help="recompute cached")
    ap.add_argument("--override", default="",
                    help="k=v[,k=v] ArchConfig overrides (perf experiments)")
    ap.add_argument("--tag", default="", help="suffix for result files")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    overrides = {}
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        overrides[k] = (v if not v.replace(".", "").replace("-", "").isdigit()
                        else (float(v) if "." in v else int(v)))

    for multi_pod in meshes:
        mesh_name = "2x16x16" if multi_pod else "16x16"
        # roofline table is single-pod only; multi-pod proves the pod axis
        roofline = (not args.no_roofline) and (not multi_pod)
        for arch in archs:
            for shape in shapes:
                name = f"{arch}_{shape}_{mesh_name}"
                if args.tag:
                    name += f"_{args.tag}"
                path = outdir / f"{name}.json"
                if path.exists() and not args.force:
                    print(f"[cached] {name}")
                    continue
                print(f"[dryrun] {name} ...", flush=True)
                t0 = time.time()
                rec = run_cell(arch, shape, multi_pod,
                               with_roofline=roofline,
                               arch_overrides=overrides or None,
                               tag_suffix=args.tag)
                rec["wall_s"] = time.time() - t0
                path.write_text(json.dumps(rec, indent=1))
                status = ("SKIP" if rec.get("skipped")
                          else "OK" if rec.get("ok") else "FAIL")
                print(f"[{status}] {name} ({rec['wall_s']:.0f}s)"
                      + (f" :: {rec.get('error', '')}" if status == "FAIL"
                         else ""), flush=True)
        if args.viking_scan:
            for name, runner in ((f"viking-scan_{mesh_name}",
                                  run_viking_scan),
                                 (f"viking-scan-batch_{mesh_name}",
                                  run_viking_scan_batch)):
                path = outdir / f"{name}.json"
                if not path.exists() or args.force:
                    rec = runner(multi_pod)
                    path.write_text(json.dumps(rec, indent=1))
                    print(f"[{'OK' if rec.get('ok') else 'FAIL'}] {name}",
                          flush=True)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
