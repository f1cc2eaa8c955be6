"""Serving launcher: open-loop directory-scoped RAG under continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --requests 32 --qps 8

Requests arrive on a seeded Poisson process at ``--qps`` and are submitted
asynchronously to the :class:`RAGServer` scheduler, which coalesces them into
device batches under the latency SLO (flush at ``--batch`` requests or when
the oldest request has waited ``--slo-ms``). Each request carries its own
prompt tokens. Latency is measured from the *scheduled* arrival time, so a
slow service cannot suppress the arrivals that would have exposed it
(coordinated-omission-safe). Between batches the namespace may be maintained
(DSM) without taking the server down — staged scope masks are epoch-validated
against racing mutations.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax

from ..configs import smoke_config
from ..datasets import make_wiki_dir
from ..models import model_schema
from ..models.layers import init_params
from ..serving import AdmissionError, SchedulerConfig, open_loop_arrivals
from ..serving.rag import ContextDatabase, RAGConfig, RAGServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--qps", type=float, default=4.0,
                    help="target offered load (Poisson arrival rate)")
    ap.add_argument("--batch", type=int, default=4,
                    help="scheduler max batch size")
    ap.add_argument("--slo-ms", type=float, default=50.0,
                    help="max wait before a partial batch is flushed")
    ap.add_argument("--queue-capacity", type=int, default=256)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--contexts", type=int, default=600)
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--scope-strategy", default="triehi",
                    choices=["triehi", "pe_online", "pe_offline"])
    args = ap.parse_args()

    dim = 64
    ds = make_wiki_dir(scale=0.003, dim=dim, n_queries=args.requests,
                       seed=args.seed)
    ctx = ContextDatabase(dim=dim, scope_strategy=args.scope_strategy)
    rng = np.random.default_rng(0)
    for i in range(min(args.contexts, ds.n_entries)):
        ctx.add_context(ds.vectors[i], ds.entry_paths[i],
                        ("L0", "L1", "L2")[i % 3],
                        rng.integers(0, 250, size=16 + 16 * (i % 3)))
    ctx.build("flat")
    cfg = smoke_config(args.arch).replace(vocab_size=256)
    params = init_params(model_schema(cfg), jax.random.PRNGKey(0),
                         cfg.param_dtype())
    server = RAGServer(ctx, params, cfg,
                       RAGConfig(k=6, token_budget=96, escalate_top=2))

    scopes = [a or "/" for a in ds.query_anchors[:args.requests]]
    # Each simulated request gets its own prompt (varying length and content)
    # so per-request prompt handling is exercised end to end.
    prompts = [rng.integers(0, 250, size=int(rng.integers(2, 12)))
               for _ in range(args.requests)]

    # One synchronous warmup batch so JIT compilation does not land inside
    # the measured window.
    n_warm = min(2, args.requests)
    server.answer(ds.queries[:n_warm], scopes[:n_warm],
                  prompts=prompts[:n_warm], max_new_tokens=args.new_tokens)

    server.start(SchedulerConfig(max_batch=args.batch,
                                 max_wait_ms=args.slo_ms,
                                 queue_capacity=args.queue_capacity),
                 max_new_tokens=args.new_tokens)
    offsets = open_loop_arrivals(args.qps, args.requests, seed=args.seed)
    t0 = time.perf_counter()
    tickets, shed = [], 0
    for i in range(args.requests):
        now = time.perf_counter() - t0
        if offsets[i] > now:
            time.sleep(offsets[i] - now)
        try:
            tickets.append(server.submit(
                ds.queries[i], scopes[i], prompt=prompts[i],
                t_arrival=t0 + offsets[i]))
        except AdmissionError:
            shed += 1
    results = [t.result(timeout=120.0) for t in tickets]
    stats = server.serving_stats()
    server.stop()

    lat = sorted(t.latency_s for t in tickets)
    scope_sizes = [r["retrieval_stats"]["scope_size"] for r in results]
    print(f"served {len(results)}/{args.requests} requests "
          f"(shed {shed}) at offered {args.qps:.1f} qps, "
          f"achieved {stats['qps']:.1f} qps")
    print(f"latency from scheduled arrival: "
          f"p50 {stats['p50_ms']:.0f} ms  p95 {stats['p95_ms']:.0f} ms  "
          f"p99 {stats['p99_ms']:.0f} ms  max {lat[-1]*1e3:.0f} ms")
    print(f"batches {stats['batches']} "
          f"(mean occupancy {stats['occupancy']:.2f}, "
          f"mean queue wait {stats['queue_mean_ms']:.0f} ms), "
          f"mean scope={np.mean(scope_sizes):.0f}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
