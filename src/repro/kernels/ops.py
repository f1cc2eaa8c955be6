"""Public wrappers for the Pallas kernels: padding, dtype plumbing, mask
packing and block-shape resolution.

Kernels run compiled on an accelerator and in interpret mode only when the
default backend is the CPU (``interpret=None``, the default, decides by
platform; pass a bool to force either). Every wrapper is traceable, so it
can be lowered for a described device without running anything.
"""
from __future__ import annotations

import functools
import threading
from collections import Counter
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .bitmap_ops import bitmap_patch as _bitmap_patch
from .bitmap_ops import mask_and_popcount as _mask_and_popcount
from .flash_decode import flash_decode as _flash_decode
from .scoped_topk import ivf_gather_topk as _ivf_gather_topk
from .scoped_topk import masked_topk as _masked_topk
from .scoped_topk import masked_topk_i8 as _masked_topk_i8
from .scoped_topk import masked_topk_pq as _masked_topk_pq


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Interpret Pallas kernels exactly when the default backend is the CPU
    (an explicit bool wins)."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() == "cpu"


_LAUNCHES: Counter = Counter()
_LAUNCHES_LOCK = threading.Lock()


def _count(kernel: str, interpret: bool) -> None:
    with _LAUNCHES_LOCK:
        _LAUNCHES[f"{kernel}:{'interpret' if interpret else 'compiled'}"] += 1


def launch_counts() -> Dict[str, int]:
    """Calls of each Pallas kernel's wrapper in this process, keyed
    ``"<kernel>:compiled"`` or ``"<kernel>:interpret"`` (a call traced
    for lowering counts too)."""
    with _LAUNCHES_LOCK:
        return dict(_LAUNCHES)


# Tuned (block_q, block_n) per wrapper, installed from a measured calibration
# artifact (vectordb.costmodel.install_kernel_tuning). Tiling is a pure
# performance knob — results are block-shape independent — so a process-global
# registry is safe; callers passing explicit block args still win.
_DEFAULT_BLOCK_Q = 8
_DEFAULT_BLOCK_N = 1024
_BLOCK_OVERRIDES: Dict[str, Tuple[int, int]] = {}
# compiled scans: the query block is the whole (padded) batch up to this
# many rows, so the row store streams from HBM once per launch; PQ keeps a
# (M, block_q, 256) LUT block resident, which bounds it lower
_COMPILED_BLOCK_Q = {"fp32": 128, "int8": 128, "pq": 64}
_MASK_LANES_ROWS = 1024     # block_n granularity of the compiled scans


def set_block_overrides(overrides: Mapping[str, Tuple[int, int]]) -> None:
    """Replace the tuned-block registry (pass ``{}`` to restore defaults)."""
    new = {str(name): (int(bq), int(bn))
           for name, (bq, bn) in dict(overrides).items()}
    _BLOCK_OVERRIDES.clear()
    _BLOCK_OVERRIDES.update(new)


def get_block_overrides() -> Dict[str, Tuple[int, int]]:
    return dict(_BLOCK_OVERRIDES)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _blocks(name: str, block_q: Optional[int],
            block_n: Optional[int]) -> Tuple[Optional[int], int]:
    """Resolve a wrapper's block shape: explicit caller args > tuned registry
    entry > hand-set defaults (``block_q=None`` leaves the query block to
    :func:`_query_block`)."""
    tuned = _BLOCK_OVERRIDES.get(name)
    if block_q is None and tuned:
        block_q = tuned[0]
    if block_n is None:
        block_n = tuned[1] if tuned else _DEFAULT_BLOCK_N
    return block_q, block_n


def _align_block_n(block_n: int, n_rows: int, floor: int = 128) -> int:
    """Clamp ``block_n`` to the (floored) row count, then round UP to a
    multiple of 32. The packed-word kernels need word-aligned blocks and a
    bare ``min(block_n, max(128, n_rows))`` clamp hands them an unaligned
    block for odd row counts (e.g. n_rows=137); rounding up is always safe
    because rows past the end are masked out."""
    block_n = min(block_n, max(floor, n_rows))
    return ((block_n + 31) // 32) * 32


def _query_block(block_q: Optional[int], nq: int, kind: str,
                 interpret: bool) -> int:
    """Query rows per block. Interpreted: the requested/tuned block (8 by
    default) clamped to the batch. Compiled: the whole batch padded to the
    sublane tiling (32 rows for int8, 8 otherwise), up to a per-kind cap —
    one HBM pass over the store per launch."""
    if interpret:
        return min(block_q or _DEFAULT_BLOCK_Q, max(1, nq))
    sub = 32 if kind == "int8" else 8
    whole = _round_up(max(nq, 1), sub)
    cap = _round_up(block_q, sub) if block_q else _COMPILED_BLOCK_Q[kind]
    return min(whole, cap)


def _scan_block_n(block_n: int, n_rows: int, interpret: bool) -> int:
    block_n = _align_block_n(block_n, n_rows)
    return block_n if interpret else _round_up(block_n, _MASK_LANES_ROWS)


def _pad_to(x, axis: int, mult: int, value=0):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value), n


def _words_to_bytes(words: jax.Array, n_bytes: int) -> jax.Array:
    """(r, W) packed uint32 words -> (r, n_bytes) f32 byte values, little-
    endian (byte j of word w covers rows 32w+8j .. 32w+8j+7), zero-padded or
    truncated to ``n_bytes``."""
    shifts = jnp.arange(4, dtype=jnp.uint32) * 8
    b = (words.astype(jnp.uint32)[..., None] >> shifts) & jnp.uint32(0xFF)
    b = b.reshape(words.shape[0], -1).astype(jnp.float32)
    if b.shape[1] < n_bytes:
        b = jnp.pad(b, [(0, 0), (0, n_bytes - b.shape[1])])
    return b[:, :n_bytes]


def _mask_to_bytes(mask: jax.Array, n_bytes: int) -> jax.Array:
    """(n,) bool/int mask -> (1, n_bytes) f32 byte values (same packing)."""
    bits = (jnp.asarray(mask) != 0).astype(jnp.int32)
    bits = jnp.pad(bits, (0, n_bytes * 8 - bits.shape[0]))
    weights = jnp.left_shift(1, jnp.arange(8, dtype=jnp.int32))
    return (bits.reshape(n_bytes, 8) * weights).sum(axis=1).astype(
        jnp.float32)[None, :]


def _query_bytes(mask_words, scope_ids, nq_pad: int, n_bytes: int):
    """Per-query mask bytes: the scope-id indirection resolved as one XLA
    row gather of the word table (padding queries read scope 0)."""
    sids = jnp.asarray(scope_ids, jnp.int32)
    sids = jnp.pad(sids, (0, nq_pad - sids.shape[0]))
    qwords = jnp.take(jnp.asarray(mask_words, jnp.uint32), sids, axis=0)
    return _words_to_bytes(qwords, n_bytes)


def _row_vector(x, n: int, default: float = 0.0) -> jax.Array:
    """Per-row scalars as a (1, n) f32 lane vector (``None`` -> zeros)."""
    if x is None or jnp.size(x) == 0:
        return jnp.full((1, n), default, jnp.float32)
    return jnp.asarray(x, jnp.float32).reshape(1, n)


def _n_bytes(n_rows: int, block_n: int) -> int:
    return -(-n_rows // block_n) * block_n // 8


# ------------------------------------------------------------------- fp32
def _fp32_scan(queries, rows, sq, mask_bytes_fn, k, metric, block_q,
               block_n, interpret):
    queries = jnp.asarray(queries, dtype=jnp.float32)
    rows = jnp.asarray(rows)
    n = rows.shape[0]
    if metric == "l2" and (sq is None or jnp.size(sq) == 0):
        rf = rows.astype(jnp.float32)
        sq = jnp.sum(rf * rf, axis=1)
    _count("masked_topk", interpret)
    block_n = _scan_block_n(block_n, n, interpret)
    block_q = _query_block(block_q, queries.shape[0], "fp32", interpret)
    qp, nq = _pad_to(queries, 0, block_q)
    mb = mask_bytes_fn(qp.shape[0], _n_bytes(n, block_n))
    vals, ids = _masked_topk(qp, rows, _row_vector(sq, n), mb, k=k,
                             block_q=block_q, block_n=block_n, metric=metric,
                             interpret=interpret)
    return vals[:nq], ids[:nq]


def scoped_topk(queries, rows, mask, k: int = 10, metric: str = "ip",
                block_q: Optional[int] = None, block_n: Optional[int] = None,
                interpret: Optional[bool] = None, sq=None
                ) -> Tuple[jax.Array, jax.Array]:
    """Masked top-k over rows: one (n,) mask shared by every query. Returns
    (values (q, k) f32 descending, ids (q, k) int32; -1 = no candidate).
    Block shapes default to the tuned registry (see
    :func:`set_block_overrides`)."""
    interpret = interpret_mode(interpret)
    block_q, block_n = _blocks("scoped_topk", block_q, block_n)
    return _fp32_scan(
        queries, rows, sq, lambda nq, nb: _mask_to_bytes(mask, nb), k,
        metric, block_q, block_n, interpret)


def multi_scope_topk(queries, rows, mask_words, scope_ids, k: int = 10,
                     metric: str = "ip", block_q: Optional[int] = None,
                     block_n: Optional[int] = None,
                     interpret: Optional[bool] = None, sq=None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Single-launch heterogeneous masked top-k: per-query scope-id
    indirection into a packed (n_scopes, ceil(n/32)) uint32 mask matrix.
    ``sq`` (optional, l2 only) is the cached squared-norm vector."""
    interpret = interpret_mode(interpret)
    block_q, block_n = _blocks("multi_scope_topk", block_q, block_n)
    return _fp32_scan(
        queries, rows, sq,
        lambda nq, nb: _query_bytes(mask_words, scope_ids, nq, nb), k,
        metric, block_q, block_n, interpret)


# ------------------------------------------------------------------- int8
def _i8_scan(q_i8, q_scale, rows_i8, row_scale, sq, mask_bytes_fn, k,
             metric, block_q, block_n, interpret):
    q_i8 = jnp.asarray(q_i8, dtype=jnp.int8)
    rows_i8 = jnp.asarray(rows_i8, dtype=jnp.int8)
    n = rows_i8.shape[0]
    _count("masked_topk_i8", interpret)
    block_n = _scan_block_n(block_n, n, interpret)
    block_q = _query_block(block_q, q_i8.shape[0], "int8", interpret)
    qp, nq = _pad_to(q_i8, 0, block_q)
    qsp, _ = _pad_to(jnp.asarray(q_scale, jnp.float32).reshape(-1, 1), 0,
                     block_q)
    mb = mask_bytes_fn(qp.shape[0], _n_bytes(n, block_n))
    vals, ids = _masked_topk_i8(
        qp, qsp, rows_i8, _row_vector(row_scale, n, 1.0),
        _row_vector(sq if metric == "l2" else None, n), mb, k=k,
        block_q=block_q, block_n=block_n, metric=metric, interpret=interpret)
    return vals[:nq], ids[:nq]


def scoped_topk_i8(q_i8, q_scale, rows_i8, row_scale, sq, mask, k: int = 10,
                   metric: str = "ip", block_q: Optional[int] = None,
                   block_n: Optional[int] = None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Masked top-k over the int8 scalar-quantized store (the scan phase of
    the two-phase int8 plan). ``sq`` holds the dequantized-row squared
    norms, read only for l2."""
    interpret = interpret_mode(interpret)
    block_q, block_n = _blocks("scoped_topk_i8", block_q, block_n)
    return _i8_scan(q_i8, q_scale, rows_i8, row_scale, sq,
                    lambda nq, nb: _mask_to_bytes(mask, nb), k, metric,
                    block_q, block_n, interpret)


def multi_scope_topk_i8(q_i8, q_scale, rows_i8, row_scale, sq, mask_words,
                        scope_ids, k: int = 10, metric: str = "ip",
                        block_q: Optional[int] = None,
                        block_n: Optional[int] = None,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Single-launch heterogeneous masked top-k over the int8 store: the
    scope-id indirection of :func:`multi_scope_topk`, int8/int32 scoring
    of :func:`scoped_topk_i8`."""
    interpret = interpret_mode(interpret)
    block_q, block_n = _blocks("multi_scope_topk_i8", block_q, block_n)
    return _i8_scan(q_i8, q_scale, rows_i8, row_scale, sq,
                    lambda nq, nb: _query_bytes(mask_words, scope_ids, nq,
                                                nb),
                    k, metric, block_q, block_n, interpret)


# --------------------------------------------------------------------- PQ
def _pq_scan(lut, codes, mask_bytes_fn, k, block_q, block_n, interpret):
    lut = jnp.asarray(lut, dtype=jnp.float32)
    codes = jnp.asarray(codes, dtype=jnp.uint8)
    n = codes.shape[0]
    _count("masked_topk_pq", interpret)
    block_n = _scan_block_n(block_n, n, interpret)
    block_q = _query_block(block_q, lut.shape[0], "pq", interpret)
    lp, nq = _pad_to(lut, 0, block_q)
    mb = mask_bytes_fn(lp.shape[0], _n_bytes(n, block_n))
    vals, ids = _masked_topk_pq(jnp.transpose(lp, (1, 0, 2)), codes.T, mb,
                                k=k, block_q=block_q, block_n=block_n,
                                interpret=interpret)
    return vals[:nq], ids[:nq]


def scoped_topk_pq(lut, codes, mask, k: int = 10,
                   block_q: Optional[int] = None,
                   block_n: Optional[int] = None,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Masked top-k over the PQ code store (the ADC scan phase of the
    two-phase PQ plan). The LUT (q, M, 256) folds the metric in, so there
    is no metric argument."""
    interpret = interpret_mode(interpret)
    block_q, block_n = _blocks("scoped_topk_pq", block_q, block_n)
    return _pq_scan(lut, codes, lambda nq, nb: _mask_to_bytes(mask, nb), k,
                    block_q, block_n, interpret)


def multi_scope_topk_pq(lut, codes, mask_words, scope_ids, k: int = 10,
                        block_q: Optional[int] = None,
                        block_n: Optional[int] = None,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Single-launch heterogeneous masked top-k over the PQ code store: the
    scope-id indirection of :func:`multi_scope_topk`, ADC scoring of
    :func:`scoped_topk_pq`."""
    interpret = interpret_mode(interpret)
    block_q, block_n = _blocks("multi_scope_topk_pq", block_q, block_n)
    return _pq_scan(lut, codes,
                    lambda nq, nb: _query_bytes(mask_words, scope_ids, nq,
                                                nb),
                    k, block_q, block_n, interpret)


# -------------------------------------------------------------------- IVF
def ivf_gather_topk(queries, probe, list_start, list_nblk, rows, ids, sq,
                    qwords, *, max_blocks: int, k: int = 10,
                    metric: str = "ip", interpret: Optional[bool] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Fused scope-masked top-k over each query's probed IVF lists, read
    straight from a list-major store: list ``c`` is blocks ``list_start[c]
    .. list_start[c] + list_nblk[c] - 1`` (``list_nblk >= 1``) of ``rows``
    ((n_blocks * block_c, d)); ``ids`` (n_blocks, block_c) are their store
    ids (-1 = padding) and ``sq`` (same shape) their squared norms (read
    only for l2). ``probe`` (B, nprobe) holds each query's lists in probe
    order, ``qwords`` (B, W) its packed scope words, and ``max_blocks``
    the longest list's block count. Each probed slot's scope bit is looked
    up here, one XLA gather over the probed ids (B x nprobe x max_blocks x
    block_c int32 — the rows themselves are never copied per query).
    Returns (values (B, k) f32, ids (B, k) int32; -1 = none)."""
    interpret = interpret_mode(interpret)
    _count("ivf_gather_topk", interpret)
    return _ivf_launch(jnp.asarray(queries, jnp.float32),
                       jnp.asarray(probe, jnp.int32),
                       jnp.asarray(list_start, jnp.int32),
                       jnp.asarray(list_nblk, jnp.int32), jnp.asarray(rows),
                       jnp.asarray(ids, jnp.int32),
                       jnp.asarray(sq, jnp.float32),
                       jnp.asarray(qwords, jnp.uint32), k=k, metric=metric,
                       max_blocks=int(max_blocks), interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "max_blocks", "interpret"))
def _ivf_launch(queries, probe, list_start, list_nblk, rows, ids, sq, qwords,
                *, k, metric, max_blocks, interpret):
    b, nprobe = probe.shape
    block_c = ids.shape[1]
    nblk = jnp.take(list_nblk, probe)[..., None]              # (B, P, 1)
    j = jnp.arange(max_blocks, dtype=jnp.int32)
    blk = jnp.take(list_start, probe)[..., None] + jnp.minimum(j, nblk - 1)
    cand = jnp.take(ids, blk, axis=0)                         # (B, P, J, c)
    cand = jnp.where((j < nblk)[..., None], cand, -1)
    safe = jnp.maximum(cand, 0).reshape(b, -1)
    words = jnp.take_along_axis(
        qwords, jnp.minimum(safe >> 5, qwords.shape[1] - 1), axis=1)
    bit = (words >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
    cid = jnp.where((cand.reshape(b, -1) >= 0) & (bit != 0),
                    cand.reshape(b, -1), -1)
    return _ivf_gather_topk(
        queries, probe.reshape(-1), list_start, list_nblk, rows,
        sq.reshape(-1, 1, block_c),
        cid.reshape(b, nprobe * max_blocks, 1, block_c), k=k,
        max_blocks=max_blocks, metric=metric, interpret=interpret)


# ----------------------------------------------------------------- bitmaps
def bitmap_patch(masks, delta, op_signs, block: int = 2048,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Batched packed-mask patch: rows with op +1 get ``| delta``, -1 get
    ``& ~delta``, 0 pass through. Pads the word axis to a block multiple
    (zero words are OR/AND-NOT neutral), unpads the result."""
    interpret = interpret_mode(interpret)
    _count("bitmap_patch", interpret)
    masks = jnp.atleast_2d(jnp.asarray(masks, dtype=jnp.uint32))
    delta = jnp.asarray(delta, dtype=jnp.uint32).reshape(1, -1)
    ops_col = jnp.asarray(op_signs, dtype=jnp.int32).reshape(-1, 1)
    if delta.shape[1] != masks.shape[1]:
        raise ValueError(f"delta has {delta.shape[1]} words for "
                         f"{masks.shape[1]}-word masks")
    block = min(block, max(8, masks.shape[1]))
    if not interpret and block < masks.shape[1]:
        block = _round_up(block, 128)
    mp, n = _pad_to(masks, 1, block)
    dp, _ = _pad_to(delta, 1, block)
    out = _bitmap_patch(mp, dp, ops_col, block=block, interpret=interpret)
    return out[:, :n]


def mask_and_popcount(a, b, block: int = 2048,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    interpret = interpret_mode(interpret)
    _count("mask_and_popcount", interpret)
    a = jnp.asarray(a, dtype=jnp.uint32)
    b = jnp.asarray(b, dtype=jnp.uint32)
    block = min(block, max(8, a.shape[0]))
    if not interpret:
        block = _round_up(block, 1024)
    ap, n = _pad_to(a, 0, block)
    bp, _ = _pad_to(b, 0, block)
    words, count = _mask_and_popcount(ap, bp, block=block, interpret=interpret)
    return words[:n], count


def flash_decode(q, k, v, length_mask=None, block_s: int = 512,
                 interpret: Optional[bool] = None) -> jax.Array:
    interpret = interpret_mode(interpret)
    _count("flash_decode", interpret)
    q = jnp.asarray(q)
    k = jnp.asarray(k)
    v = jnp.asarray(v)
    b, _, s, _ = k.shape
    if length_mask is None:
        length_mask = jnp.ones((b, s), dtype=jnp.int8)
    block_s = min(block_s, max(128, s))
    kp, _ = _pad_to(k, 2, block_s)
    vp, _ = _pad_to(v, 2, block_s)
    mp, _ = _pad_to(jnp.asarray(length_mask, jnp.int8), 1, block_s, value=0)
    return _flash_decode(q, kp, vp, mp, block_s=block_s, interpret=interpret)


__all__ = ["scoped_topk", "scoped_topk_i8", "scoped_topk_pq",
           "multi_scope_topk", "multi_scope_topk_i8", "multi_scope_topk_pq",
           "ivf_gather_topk", "mask_and_popcount", "bitmap_patch",
           "flash_decode", "set_block_overrides", "get_block_overrides",
           "interpret_mode", "launch_counts", "ref"]
