"""Training launcher: checkpoint-restart, deterministic data replay, async
saves, elastic mesh — the fault-tolerance story in one driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt

Restart semantics: on start, the driver restores the newest manifested
checkpoint (possibly saved on a *different* mesh shape — leaves are stored as
global arrays and re-placed under the current mesh's shardings) and resumes at
step+1 with bit-identical batches (data is a pure function of step).
Straggler mitigation at this layer: steps are synchronous SPMD, so per-step
wall time is max over hosts; the launcher logs a rolling p95 and flags slow
steps — on a real cluster the flagged host is drained and the job restarts
elastically from the last checkpoint (see DESIGN.md §5).
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

from ..configs import get_arch, smoke_config
from ..launch.mesh import make_mesh_for_devices
from ..models import model_schema
from ..models.layers import init_params, logical_tree
from ..models.common import logical_spec
from ..training.checkpoint import CheckpointManager
from ..training.data import DataConfig, SyntheticLMData
from ..training.optimizer import OptConfig, init_opt_state
from ..training.train_step import make_train_step
from jax.sharding import NamedSharding


def shardings_for(tree, logical, mesh):
    return jax.tree.map(
        lambda x, lg: NamedSharding(mesh,
                                    logical_spec(lg, np.shape(x), mesh)),
        tree, logical)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    mesh = make_mesh_for_devices(model_parallelism=args.model_parallel)
    print(f"arch={cfg.name} params={cfg.param_count():,} mesh={dict(mesh.shape)}")

    schema = model_schema(cfg)
    params = init_params(schema, jax.random.PRNGKey(0), cfg.param_dtype())
    opt_state = init_opt_state(params)
    opt_cfg = OptConfig(lr=args.lr, total_steps=args.steps,
                        warmup_steps=max(1, args.steps // 10))
    data = SyntheticLMData(DataConfig(cfg.vocab_size, args.seq, args.batch))
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, mesh,
                                      accum_steps=args.accum),
                      donate_argnums=(0, 1))

    start = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir)
        if ckpt.latest_step() is not None:
            lg = logical_tree(schema)
            sh = shardings_for(params, lg, mesh)
            state, start, _ = ckpt.restore(
                {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            params = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                  params, sh)
            start += 1
            print(f"restored checkpoint, resuming at step {start}")

    times = []
    with mesh:
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
            t0 = time.perf_counter()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            times.append(dt)
            if len(times) > 20:
                times.pop(0)
            p95 = float(np.percentile(times, 95))
            if dt > 3 * p95 and len(times) >= 10:
                print(f"[straggler-warning] step {step}: {dt:.2f}s vs p95 "
                      f"{p95:.2f}s — drain candidate")
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} {dt*1e3:.0f}ms")
            if ckpt and (step % args.ckpt_every == 0 or
                         step == args.steps - 1):
                ckpt.save_async(step, {"params": params, "opt": opt_state})
    if ckpt:
        ckpt.wait()
    print("done")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
