"""Pallas kernel microbenchmarks (interpret mode on CPU — wall numbers are
for regression tracking only; the kernels target TPU VMEM/MXU execution).

Reports the kernel wall time next to the pure-jnp reference at equal shapes,
plus the analytic VMEM working set per grid step (the number that must stay
under ~16 MB on a v5e core for the BlockSpec choice to be valid).
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import jax.numpy as jnp

from repro.kernels import ops, ref


def _block(out):
    import jax
    return jax.block_until_ready(out)


def _t(fn, *args, repeat=3):
    _block(fn(*args))              # compile/trace once
    t0 = time.perf_counter_ns()
    for _ in range(repeat):
        _block(fn(*args))
    return (time.perf_counter_ns() - t0) / 1e3 / repeat


def run() -> List[Dict]:
    rng = np.random.default_rng(0)
    rows = []
    # scoped_topk: q=8 queries over 16k x 256 store, 30% scope
    q, n, d, k = 8, 16384, 256, 10
    Q = rng.normal(size=(q, d)).astype(np.float32)
    X = rng.normal(size=(n, d)).astype(np.float32)
    m = rng.random(n) < 0.3
    block_n = 1024
    vmem = (block_n * d * 4 + q * d * 4 + q * k * 8) / 2 ** 20
    t_kernel = _t(lambda: ops.scoped_topk(Q, X, m, k=k), repeat=1)
    t_ref = _t(lambda: ref.scoped_topk_ref(jnp.asarray(Q), jnp.asarray(X),
                                           jnp.asarray(m), k=k))
    rows.append({"name": "kernels/scoped_topk/16k x 256",
                 "us_per_call": t_kernel,
                 "derived": f"ref_us={t_ref:.0f};vmem_mb={vmem:.1f}"})
    # bitmap popcount: 1M-bit masks
    a = rng.integers(0, 2 ** 32, size=32768, dtype=np.uint32)
    b = rng.integers(0, 2 ** 32, size=32768, dtype=np.uint32)
    t_kernel = _t(lambda: ops.mask_and_popcount(a, b), repeat=1)
    t_ref = _t(lambda: ref.mask_and_popcount_ref(jnp.asarray(a),
                                                 jnp.asarray(b)))
    rows.append({"name": "kernels/mask_and_popcount/1Mbit",
                 "us_per_call": t_kernel, "derived": f"ref_us={t_ref:.0f}"})
    # flash decode: b=4 h=16 kv=4 s=4096 d=64
    bsz, h, kv, s, d_ = 4, 16, 4, 4096, 64
    qv = rng.normal(size=(bsz, h, d_)).astype(np.float32)
    kc = rng.normal(size=(bsz, kv, s, d_)).astype(np.float32)
    vc = rng.normal(size=(bsz, kv, s, d_)).astype(np.float32)
    vmem = (2 * 512 * d_ * 4 + (h // kv) * d_ * 4) / 2 ** 20
    t_kernel = _t(lambda: ops.flash_decode(qv, kc, vc), repeat=1)
    t_ref = _t(lambda: ref.flash_decode_ref(
        jnp.asarray(qv), jnp.asarray(kc), jnp.asarray(vc),
        jnp.ones((bsz, s), jnp.int8)))
    rows.append({"name": "kernels/flash_decode/4x16x4096",
                 "us_per_call": t_kernel,
                 "derived": f"ref_us={t_ref:.0f};vmem_mb={vmem:.2f}"})
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from .common import emit
    emit(run())
