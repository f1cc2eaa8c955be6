"""Pallas TPU kernels: fused scope-masked distance + running top-k scan.

The compute hot-spot of a directory-scoped vector search (DSQ after scope
resolution) is "score my query batch against every candidate row and keep the
k best". On TPU the roofline-optimal shape is a *streamed block scan*:

  HBM -> VMEM : row tile (block_n, d), per-query scope-mask bytes
                (block_q, block_n/8)
  MXU         : S = Q · Xᵀ                       (block_q, block_n)
  MXU         : mask bytes -> one byte per row lane (0/1 expansion matrix)
  VPU         : S = where(bit, S, -inf); merge into running top-k scratch

The running (block_q, k) best values/ids live in VMEM scratch across the whole
n-sweep, so the (q, n) score matrix is never materialized in HBM.

Layout rules the TPU compiler (Mosaic) imposes, and how each kernel keeps
them: every block's last dim is a multiple of 128 lanes or the whole array,
its second-to-last a multiple of the dtype's sublane tiling or the whole
array, and nothing gathers inside a kernel. So

* scope masks reach the kernel as per-query byte values (uint32 words split
  into bytes, one f32 per byte): a ``block_n`` row tile reads
  ``block_n / 8`` byte lanes (128 at ``block_n = 1024``), and a 0/1
  expansion matmul broadcasts byte ``j`` onto row lanes ``8j..8j+7`` —
  exact, since exactly one product per output is non-zero;
* the scope-id indirection (``scope_ids`` into the packed mask table) runs
  in the jitted wrapper as one XLA row gather of the small word table;
* per-row scalars (int8 scales, squared norms) are ``(1, n)`` lane vectors;
* the PQ codes are scored by one-hot matmuls (one 256-wide one-hot per
  subspace against that subspace's LUT row), never by a lane gather.

Grid: (q_blocks, n_blocks), n innermost so the scratch accumulates over n and
is flushed to the output block once per q block at the last n step. In
interpret mode (CPU) any shape works; compiled, ``d % 128 == 0`` and
``block_n % 1024 == 0``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)
_ID_MAX = int(np.iinfo(np.int32).max)
_HIGHEST = jax.lax.Precision.HIGHEST

# Scoped-VMEM ceiling handed to Mosaic: v5e has 128 MiB of VMEM per core but
# the compiler's default scope is 16 MiB, which a double-buffered fp32 row
# tile at d=1024 nearly fills on its own.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _compiler_params(n_grid: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (n_grid - 1) + ("arbitrary",),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _lowest(keys, hit):
    return jnp.min(jnp.where(hit, keys, _ID_MAX), axis=1, keepdims=True)


def _merge_topk(acc_v, acc_i, scores, ids, k: int, unique_ids: bool = True):
    """Merge a (q, m) scored block into the (q, k) running best: k rounds of
    (row max, first maximum, knock it out). "First" is in the order of the
    running best followed by the block — the tie order of
    ``jax.lax.top_k`` over a row scanned left to right. Masked lanes carry
    ``NEG_INF``; a round whose max is ``NEG_INF`` has run out of
    candidates and emits ``(NEG_INF, -1)``. With ``unique_ids`` (the scans,
    whose ids are ascending row numbers) the ids themselves order the
    candidates; otherwise (the IVF scan, which visits lists in probe
    order) scan positions do.
    Only lane reductions and selects: no argmax, concat or scatter."""
    lane = jax.lax.broadcasted_iota(jnp.int32, acc_v.shape, 1)
    if unique_ids:
        acc_key, key = acc_i, ids
    else:
        acc_key = lane
        key = k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    out_v = jnp.full(acc_v.shape, NEG_INF, jnp.float32)
    out_i = jnp.full(acc_i.shape, -1, jnp.int32)
    for j in range(k):
        m = jnp.maximum(jnp.max(acc_v, axis=1, keepdims=True),
                        jnp.max(scores, axis=1, keepdims=True))
        win = jnp.minimum(_lowest(acc_key, acc_v == m),
                          _lowest(key, scores == m))
        live = m > NEG_INF
        if unique_ids:
            win_id = win
        else:
            win_id = jnp.maximum(
                jnp.max(jnp.where(acc_key == win, acc_i, -1), axis=1,
                        keepdims=True),
                jnp.max(jnp.where(key == win, ids, -1), axis=1,
                        keepdims=True))
        out_v = jnp.where(lane == j, jnp.where(live, m, NEG_INF), out_v)
        out_i = jnp.where(lane == j, jnp.where(live, win_id, -1), out_i)
        acc_v = jnp.where(acc_key == win, -jnp.inf, acc_v)
        scores = jnp.where(key == win, -jnp.inf, scores)
    return out_v, out_i


def _mask_bits(qbytes, block_n: int):
    """(r, block_n/8) byte values (f32, 0..255) -> (r, block_n) bool: byte j
    covers rows 8j..8j+7, bit (row & 7) — the little-endian packing of
    ``RoaringBitmap.to_words``. The byte->lane broadcast is a 0/1 matmul:
    each output sums exactly one byte times 1, so bf16 operands are exact."""
    nb = block_n // 8
    j = jax.lax.broadcasted_iota(jnp.int32, (nb, block_n), 0)
    r = jax.lax.broadcasted_iota(jnp.int32, (nb, block_n), 1)
    expand = ((r >> 3) == j).astype(jnp.bfloat16)
    vals = jax.lax.dot_general(
        qbytes.astype(jnp.bfloat16), expand, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # (r, block_n)
    lane = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    return ((vals.astype(jnp.int32) >> (lane & 7)) & 1) != 0


def _finish(scores, bytes_ref, acc_v, acc_i, vals_ref, ids_ref, *, k, block_n):
    """Shared tail of every scan kernel: mask, merge, flush."""
    ni = pl.program_id(1)
    valid = _mask_bits(bytes_ref[...], block_n)        # (1 | block_q, block_n)
    valid = jnp.broadcast_to(valid, scores.shape)
    scores = jnp.where(valid, scores, NEG_INF)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    ids = jnp.where(valid, ni * block_n + col, -1)
    new_v, new_i = _merge_topk(acc_v[...], acc_i[...], scores, ids, k)
    acc_v[...] = new_v
    acc_i[...] = new_i

    @pl.when(ni == pl.num_programs(1) - 1)
    def _flush():
        vals_ref[...] = acc_v[...]
        ids_ref[...] = acc_i[...]


def _init(acc_v, acc_i):
    @pl.when(pl.program_id(1) == 0)
    def _():
        acc_v[...] = jnp.full_like(acc_v, NEG_INF)
        acc_i[...] = jnp.full_like(acc_i, -1)


def _scan_kernel(q_ref, x_ref, sq_ref, bytes_ref, vals_ref, ids_ref,
                 acc_v, acc_i, *, k: int, block_n: int, metric: str):
    """fp32 scan: exact fp32 scores (``Precision.HIGHEST``)."""
    _init(acc_v, acc_i)
    scores = jax.lax.dot_general(
        q_ref[...], x_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        precision=_HIGHEST,
        preferred_element_type=jnp.float32)                   # (block_q, block_n)
    if metric == "l2":
        scores = 2.0 * scores - sq_ref[...]
    _finish(scores, bytes_ref, acc_v, acc_i, vals_ref, ids_ref, k=k,
            block_n=block_n)


def _scan_kernel_i8(q_ref, qs_ref, x_ref, rs_ref, sq_ref, bytes_ref,
                    vals_ref, ids_ref, acc_v, acc_i, *, k: int, block_n: int,
                    metric: str):
    """int8 twin of :func:`_scan_kernel`: the MXU accumulates the int8 codes
    in int32 (exact, d * 127^2 << 2^31) and the symmetric per-row scales
    multiply back in at merge time, so the streamed HBM->VMEM tile is a
    quarter of the fp32 bytes. The l2 term streams the precomputed
    dequantized-row norms."""
    _init(acc_v, acc_i)
    s32 = jax.lax.dot_general(
        q_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                     # (block_q, block_n)
    scores = s32.astype(jnp.float32) * (qs_ref[...] * rs_ref[...])
    if metric == "l2":
        scores = 2.0 * scores - sq_ref[...]
    _finish(scores, bytes_ref, acc_v, acc_i, vals_ref, ids_ref, k=k,
            block_n=block_n)


def _scan_kernel_pq(lut_ref, codes_ref, bytes_ref, vals_ref, ids_ref,
                    acc_v, acc_i, *, k: int, block_n: int, group: int):
    """PQ/ADC twin: the streamed tile is the (M, block_n) code tile — M bytes
    per row — and each subspace scores through a (block_q, 256) LUT row
    times a (256, block_n) one-hot of its codes. Exactly one one-hot entry
    per output is 1, so with ``Precision.HIGHEST`` every subspace term is
    the exact LUT entry and the sum runs over subspaces in order. The LUT
    folds the metric in, so the kernel is metric-free."""
    _init(acc_v, acc_i)
    m_total = codes_ref.shape[0]
    block_q = lut_ref.shape[1]
    cent = jax.lax.broadcasted_iota(jnp.int32, (256, block_n), 0)

    def body(g, scores):
        base = pl.multiple_of(g * group, group)
        codes = codes_ref[pl.ds(base, group), :].astype(jnp.int32)
        for i in range(group):
            onehot = (cent == codes[i:i + 1, :]).astype(jnp.float32)
            scores = scores + jax.lax.dot_general(
                lut_ref[base + i], onehot, (((1,), (0,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=jnp.float32)
        return scores

    scores = jax.lax.fori_loop(
        0, m_total // group, body,
        jnp.zeros((block_q, block_n), jnp.float32))
    _finish(scores, bytes_ref, acc_v, acc_i, vals_ref, ids_ref, k=k,
            block_n=block_n)


def _bytes_spec(n_mask_rows: int, block_q: int, block_n: int):
    """Mask-byte block: shared ``(1, n/8)`` mask or per-query ``(q, n/8)``."""
    if n_mask_rows == 1:
        return pl.BlockSpec((1, block_n // 8), lambda qi, ni: (0, ni))
    return pl.BlockSpec((block_q, block_n // 8), lambda qi, ni: (qi, ni))


def _out_specs(block_q: int, k: int):
    return [pl.BlockSpec((block_q, k), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_q, k), lambda qi, ni: (qi, 0))]


def _out_shapes(nq: int, k: int):
    return [jax.ShapeDtypeStruct((nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, k), jnp.int32)]


def _scratch(block_q: int, k: int):
    return [pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.int32)]


def _check_grid(nq, n, n_bytes, block_q, block_n, interpret):
    assert nq % block_q == 0, (nq, block_q)
    assert block_n % 8 == 0, block_n
    n_blocks = pl.cdiv(n, block_n)
    assert n_bytes == n_blocks * block_n // 8, (n_bytes, n, block_n)
    assert interpret or block_n % 1024 == 0, (
        "compiled scans read block_n/8 mask-byte lanes: block_n % 1024")
    return (nq // block_q, n_blocks)


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_q", "block_n", "metric", "interpret"))
def masked_topk(queries: jax.Array, rows: jax.Array, sq: jax.Array,
                mask_bytes: jax.Array, k: int = 10, block_q: int = 8,
                block_n: int = 1024, metric: str = "ip",
                interpret: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Fused masked top-k over the fp32 (or bf16) store.

    queries (q, d) f32; rows (n, d); sq (1, n) f32 squared row norms (read
    only for l2); mask_bytes (1 or q, n_blocks * block_n / 8) f32 byte
    values of the packed scope mask (one shared row, or one per query).
    Returns (values (q, k) f32 descending, ids (q, k) int32; -1 = none).
    ``q % block_q == 0`` (ops.py pads); rows past ``n`` in the last block
    are masked by their zero mask bytes.
    """
    nq, d = queries.shape
    n = rows.shape[0]
    assert d % 128 == 0 or interpret, "lane-dim should be 128-aligned on TPU"
    grid = _check_grid(nq, n, mask_bytes.shape[1], block_q, block_n,
                       interpret)
    kernel = functools.partial(_scan_kernel, k=k, block_n=block_n,
                               metric=metric)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_n, d), lambda qi, ni: (ni, 0)),
            pl.BlockSpec((1, block_n), lambda qi, ni: (0, ni)),
            _bytes_spec(mask_bytes.shape[0], block_q, block_n),
        ],
        out_specs=_out_specs(block_q, k),
        out_shape=_out_shapes(nq, k),
        scratch_shapes=_scratch(block_q, k),
        compiler_params=None if interpret else _compiler_params(2),
        interpret=interpret,
        name="masked_topk",
    )(queries.astype(jnp.float32), rows, sq.astype(jnp.float32),
      mask_bytes.astype(jnp.float32))


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_q", "block_n", "metric", "interpret"))
def masked_topk_i8(q_i8: jax.Array, q_scale: jax.Array, rows_i8: jax.Array,
                   row_scale: jax.Array, sq: jax.Array, mask_bytes: jax.Array,
                   k: int = 10, block_q: int = 8, block_n: int = 1024,
                   metric: str = "ip", interpret: bool = True
                   ) -> Tuple[jax.Array, jax.Array]:
    """Fused masked top-k over the int8 scalar-quantized store.

    q_i8 (q, d) int8; q_scale (q, 1) f32; rows_i8 (n, d) int8; row_scale
    (1, n) f32; sq (1, n) f32 dequantized squared norms (l2 only);
    mask_bytes as :func:`masked_topk`. Same returns and preconditions.
    """
    nq, d = q_i8.shape
    n = rows_i8.shape[0]
    assert d % 128 == 0 or interpret, "lane-dim should be 128-aligned on TPU"
    grid = _check_grid(nq, n, mask_bytes.shape[1], block_q, block_n,
                       interpret)
    kernel = functools.partial(_scan_kernel_i8, k=k, block_n=block_n,
                               metric=metric)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_q, 1), lambda qi, ni: (qi, 0)),
            pl.BlockSpec((block_n, d), lambda qi, ni: (ni, 0)),
            pl.BlockSpec((1, block_n), lambda qi, ni: (0, ni)),
            pl.BlockSpec((1, block_n), lambda qi, ni: (0, ni)),
            _bytes_spec(mask_bytes.shape[0], block_q, block_n),
        ],
        out_specs=_out_specs(block_q, k),
        out_shape=_out_shapes(nq, k),
        scratch_shapes=_scratch(block_q, k),
        compiler_params=None if interpret else _compiler_params(2),
        interpret=interpret,
        name="masked_topk_i8",
    )(q_i8.astype(jnp.int8), q_scale.astype(jnp.float32),
      rows_i8.astype(jnp.int8), row_scale.astype(jnp.float32),
      sq.astype(jnp.float32), mask_bytes.astype(jnp.float32))


@functools.partial(
    jax.jit, static_argnames=("k", "block_q", "block_n", "interpret"))
def masked_topk_pq(lut_t: jax.Array, codes_t: jax.Array,
                   mask_bytes: jax.Array, k: int = 10, block_q: int = 8,
                   block_n: int = 1024, interpret: bool = True
                   ) -> Tuple[jax.Array, jax.Array]:
    """Fused masked top-k over the PQ code store (ADC scan phase).

    lut_t (M, q, 256) f32 per-query ADC tables, subspace-major (metric
    folded in — see ``vectordb.quant.PQCodebook.lut``); codes_t (M, n)
    uint8 codes, subspace-major; mask_bytes as :func:`masked_topk`. Same
    returns and preconditions.
    """
    m, nq, n_cent = lut_t.shape
    n = codes_t.shape[1]
    assert n_cent == 256 and codes_t.shape[0] == m, (lut_t.shape,
                                                     codes_t.shape)
    grid = _check_grid(nq, n, mask_bytes.shape[1], block_q, block_n,
                       interpret)
    # uint8 codes tile 32 sublanes deep: read them in 32-subspace groups
    group = 32 if m % 32 == 0 else m
    kernel = functools.partial(_scan_kernel_pq, k=k, block_n=block_n,
                               group=group)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_q, 256), lambda qi, ni: (0, qi, 0)),
            pl.BlockSpec((m, block_n), lambda qi, ni: (0, ni)),
            _bytes_spec(mask_bytes.shape[0], block_q, block_n),
        ],
        out_specs=_out_specs(block_q, k),
        out_shape=_out_shapes(nq, k),
        scratch_shapes=_scratch(block_q, k),
        compiler_params=None if interpret else _compiler_params(2),
        interpret=interpret,
        name="masked_topk_pq",
    )(lut_t.astype(jnp.float32), codes_t.astype(jnp.uint8),
      mask_bytes.astype(jnp.float32))


def _ivf_kernel(probe_ref, start_ref, nblk_ref, q_ref, x_ref, sq_ref,
                cid_ref, vals_ref, ids_ref, acc_v, acc_i, *, k: int,
                nprobe: int, metric: str):
    """Batched-IVF scan: grid (query, probe, block). Each step streams one
    block of the query's ``probe``-th list straight from the list-major row
    store — the block index comes from the scalar-prefetched probe table
    and list offsets, so no per-query candidate copy exists in HBM. Blocks
    past the list's end repeat its last block index (no new DMA) and are
    skipped. ``cid`` carries the candidate's store id, or -1 where the slot
    is list padding or outside the query's scope."""
    b, p, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((p == 0) & (j == 0))
    def _():
        acc_v[...] = jnp.full_like(acc_v, NEG_INF)
        acc_i[...] = jnp.full_like(acc_i, -1)

    @pl.when(j < nblk_ref[probe_ref[b * nprobe + p]])
    def _():
        scores = jax.lax.dot_general(
            q_ref[0], x_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)               # (1, block_c)
        if metric == "l2":
            scores = 2.0 * scores - sq_ref[0]
        ids = cid_ref[0, 0]
        scores = jnp.where(ids >= 0, scores, NEG_INF)
        new_v, new_i = _merge_topk(acc_v[...], acc_i[...], scores, ids, k,
                                   unique_ids=False)
        acc_v[...] = new_v
        acc_i[...] = new_i

    @pl.when((p == pl.num_programs(1) - 1) & (j == pl.num_programs(2) - 1))
    def _flush():
        vals_ref[0] = acc_v[...]
        ids_ref[0] = acc_i[...]


@functools.partial(
    jax.jit, static_argnames=("k", "max_blocks", "metric", "interpret"))
def ivf_gather_topk(queries: jax.Array, probe: jax.Array,
                  list_start: jax.Array, list_nblk: jax.Array,
                  rows: jax.Array, sq: jax.Array, cand_ids: jax.Array,
                  k: int = 10, max_blocks: int = 1, metric: str = "ip",
                  interpret: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Fused scope-masked top-k over each query's probed IVF lists.

    queries (B, d) f32; probe (B * nprobe,) int32 probed list per (query,
    rank); list_start / list_nblk (n_lists,) int32 first block and block
    count (>= 1) of each list in the list-major store; rows (n_blocks *
    block_c, d) the list-major rows; sq (n_blocks, 1, block_c) f32 their
    squared norms (l2 only); cand_ids (B, nprobe * max_blocks, 1, block_c)
    int32 store id of each probed slot, -1 for padding or out of scope.
    Returns (values (B, k) f32, ids (B, k) int32; -1 = none); ties keep
    the probe-rank / in-list order of the scan.
    """
    B, d = queries.shape
    nprobe = probe.shape[0] // B
    block_c = cand_ids.shape[-1]
    assert cand_ids.shape == (B, nprobe * max_blocks, 1, block_c), (
        cand_ids.shape, B, nprobe, max_blocks)
    assert d % 128 == 0 or interpret, "lane-dim should be 128-aligned on TPU"
    assert interpret or block_c % 128 == 0, block_c

    def row_block(b, p, j, pr, st, nb):
        lst = pr[b * nprobe + p]
        return st[lst] + jnp.minimum(j, nb[lst] - 1)

    kernel = functools.partial(_ivf_kernel, k=k, nprobe=nprobe,
                               metric=metric)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nprobe, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda b, p, j, *_: (b, 0, 0)),
            pl.BlockSpec((block_c, d),
                         lambda *a: (row_block(*a), 0)),
            pl.BlockSpec((1, 1, block_c),
                         lambda *a: (row_block(*a), 0, 0)),
            pl.BlockSpec((1, 1, 1, block_c),
                         lambda b, p, j, *_: (b, p * max_blocks + j, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, 1, k), lambda b, p, j, *_: (b, 0, 0)),
                   pl.BlockSpec((1, 1, k), lambda b, p, j, *_: (b, 0, 0))],
        scratch_shapes=_scratch(1, k),
    )
    vals, ids = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, k), jnp.int32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ivf_gather_topk",
    )(probe.astype(jnp.int32), list_start.astype(jnp.int32),
      list_nblk.astype(jnp.int32), queries.astype(jnp.float32)[:, None, :],
      rows, sq.astype(jnp.float32), cand_ids.astype(jnp.int32))
    return vals[:, 0], ids[:, 0]
