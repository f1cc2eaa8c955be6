"""The main-path kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers and compiles for a described (not attached)
``v5e:2x2`` topology, which is what the TPU compiler would refuse on the chip
— unaligned blocks, in-kernel gathers, too much VMEM. Shapes are the WIKI-Dir
serving width (d=1024) over a 262,144-row store with a 64-request batch.

The topology is described inside a module fixture (never at import), so every
pytest worker collects the same tests and only the worker that runs this file
loads the TPU compiler. The persistent compilation cache is off here: an entry
compiled for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

N, D, B, K, R = 262_144, 1024, 64, 10, 40
W = N // 32
M = 256                      # PQ subspaces at d=1024 (dsub=4)
N_SCOPES = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo: str) -> None:
    assert "tpu_custom_call" in hlo, "no Pallas kernel in the compiled program"


def test_multi_scope_topk_compiles(spec):
    from repro.kernels import ops
    hlo = _compile(
        lambda q, x, w, s: ops.multi_scope_topk(q, x, w, s, k=K,
                                                interpret=False),
        spec((B, D), jnp.float32), spec((N, D), jnp.float32),
        spec((N_SCOPES, W), jnp.uint32), spec((B,), jnp.int32))
    _assert_kernel(hlo)


def test_scoped_topk_i8_compiles(spec):
    from repro.kernels import ops
    hlo = _compile(
        lambda q, qs, x, rs, sq, m: ops.scoped_topk_i8(
            q, qs, x, rs, sq, m, k=R, metric="l2", interpret=False),
        spec((B, D), jnp.int8), spec((B,), jnp.float32),
        spec((N, D), jnp.int8), spec((N,), jnp.float32),
        spec((N,), jnp.float32), spec((N,), jnp.bool_))
    _assert_kernel(hlo)


def test_multi_scope_topk_pq_compiles(spec):
    from repro.kernels import ops
    hlo = _compile(
        lambda lut, c, w, s: ops.multi_scope_topk_pq(lut, c, w, s, k=R,
                                                     interpret=False),
        spec((B, M, 256), jnp.float32), spec((N, M), jnp.uint8),
        spec((N_SCOPES, W), jnp.uint32), spec((B,), jnp.int32))
    _assert_kernel(hlo)


def test_ivf_gather_topk_compiles(spec):
    """The IVF list scan at a full 64-request batch: sqrt(n) lists, the
    default 8 probes, lists of up to 8 blocks streamed from the list-major
    store by block index (no per-query candidate copy)."""
    from repro.kernels import ops
    from repro.vectordb.ivf import LIST_BLOCK
    n_lists, nprobe, max_blocks = 512, 8, 8
    n_blocks = N // LIST_BLOCK + n_lists
    hlo = _compile(
        lambda q, pr, st, nb, rows, ids, sq, w: ops.ivf_gather_topk(
            q, pr, st, nb, rows, ids, sq, w, max_blocks=max_blocks, k=K,
            metric="l2", interpret=False),
        spec((B, D), jnp.float32), spec((B, nprobe), jnp.int32),
        spec((n_lists,), jnp.int32), spec((n_lists,), jnp.int32),
        spec((n_blocks * LIST_BLOCK, D), jnp.float32),
        spec((n_blocks, LIST_BLOCK), jnp.int32),
        spec((n_blocks, LIST_BLOCK), jnp.float32), spec((B, W), jnp.uint32))
    _assert_kernel(hlo)


def test_bitmap_patch_compiles(spec):
    from repro.kernels import ops
    hlo = _compile(
        lambda m, d, o: ops.bitmap_patch(m, d, o, interpret=False),
        spec((N_SCOPES, W), jnp.uint32), spec((W,), jnp.uint32),
        spec((N_SCOPES,), jnp.int32))
    _assert_kernel(hlo)


def test_jnp_multi_scan_topk_compiles(spec):
    """The default (non-Pallas) flat scan: plain XLA, no kernel."""
    from repro.vectordb.flat import _multi_scan_topk
    hlo = _compile(
        lambda q, x, sq, w, s: _multi_scan_topk(q, x, sq, w, s, K, "ip"),
        spec((B, D), jnp.float32), spec((N, D), jnp.float32),
        spec((0,), jnp.float32), spec((N_SCOPES, W), jnp.uint32),
        spec((B,), jnp.int32))
    assert "tpu_custom_call" not in hlo


def test_sharded_scan_compiles_on_4_chips(topo):
    """The serving tier's sharded scan on a 4-chip mesh: rows split over
    every device, one all-gather of the per-shard top-k triples."""
    from repro.distributed.search import (make_sharded_batch_search,
                                          multi_scope_search_input_specs)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    fn = make_sharded_batch_search(mesh, N, D, K)
    args, shardings = multi_scope_search_input_specs(mesh, N, D, B, N_SCOPES)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
            for a, s in zip(args, shardings)]
    compiled = fn.lower(*args).compile()
    assert "all-gather" in compiled.as_text()
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert per_chip < N * D * 4 / 2, "rows must be split over the chips"
