"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` uses only jax.numpy / lax high-level ops, no Pallas, and is the
target of the per-kernel shape/dtype sweep tests (assert_allclose).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = float(np.finfo(np.float32).min)


def scoped_topk_ref(queries: jax.Array, rows: jax.Array, mask: jax.Array,
                    k: int = 10, metric: str = "ip"
                    ) -> Tuple[jax.Array, jax.Array]:
    """Unfused reference: materializes the full (q, n) score matrix."""
    queries = queries.astype(jnp.float32)
    rows_f = rows.astype(jnp.float32)
    scores = queries @ rows_f.T
    if metric == "l2":
        scores = 2.0 * scores - jnp.sum(rows_f * rows_f, axis=1)[None, :]
    valid = mask.astype(bool)
    scores = jnp.where(valid[None, :], scores, NEG_INF)
    vals, ids = jax.lax.top_k(scores, k)
    ids = jnp.where(vals <= NEG_INF, -1, ids)
    return vals, ids.astype(jnp.int32)


def unpack_words_ref(words: jax.Array, n: int) -> jax.Array:
    """(..., n/32) packed uint32 -> (..., n) bool (bit j of word w = row
    w*32+j, little-endian like RoaringBitmap.to_words)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & jnp.uint32(1)
    return (bits.reshape(*words.shape[:-1], -1) != 0)[..., :n]


def multi_scope_topk_ref(queries: jax.Array, rows: jax.Array,
                         mask_words: jax.Array, scope_ids: jax.Array,
                         k: int = 10, metric: str = "ip"
                         ) -> Tuple[jax.Array, jax.Array]:
    """Unfused heterogeneous-batch reference: expands every scope's packed
    mask to a dense bool matrix, gathers per-query rows, full score matrix."""
    queries = queries.astype(jnp.float32)
    rows_f = rows.astype(jnp.float32)
    n = rows_f.shape[0]
    scores = queries @ rows_f.T
    if metric == "l2":
        scores = 2.0 * scores - jnp.sum(rows_f * rows_f, axis=1)[None, :]
    masks = unpack_words_ref(mask_words, n)           # (n_scopes, n)
    valid = jnp.take(masks, scope_ids, axis=0)        # (q, n)
    scores = jnp.where(valid, scores, NEG_INF)
    vals, ids = jax.lax.top_k(scores, k)
    ids = jnp.where(vals <= NEG_INF, -1, ids)
    return vals, ids.astype(jnp.int32)


def ivf_gather_topk_ref(queries: np.ndarray, probe: np.ndarray,
                        list_start: np.ndarray, list_nblk: np.ndarray,
                        rows: np.ndarray, ids: np.ndarray, qwords: np.ndarray,
                        k: int = 10, metric: str = "ip"
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Unfused numpy oracle for the batched-IVF list scan: per query,
    concatenate the probed lists' blocks of the list-major store in probe
    order, score every slot, expand the query's packed scope words, full
    stable sort. ``ids`` -1 marks list padding."""
    q = np.asarray(queries, dtype=np.float32)
    x = np.asarray(rows, dtype=np.float32)
    table = np.asarray(ids, dtype=np.int64)
    block = table.shape[1]
    words = np.asarray(qwords, dtype=np.uint32)
    out_v = np.full((len(q), k), NEG_INF, np.float32)
    out_i = np.full((len(q), k), -1, np.int32)
    for b in range(len(q)):
        blks = np.concatenate([
            np.arange(list_start[c], list_start[c] + list_nblk[c])
            for c in np.asarray(probe)[b]])
        cand = table[blks].reshape(-1)
        xr = x.reshape(-1, block, x.shape[1])[blks].reshape(-1, x.shape[1])
        scores = xr @ q[b]
        if metric == "l2":
            scores = 2.0 * scores - np.sum(xr * xr, axis=1)
        safe = np.maximum(cand, 0)
        bits = (words[b, safe >> 5] >> (safe & 31).astype(np.uint32)) & 1
        scores = np.where((cand >= 0) & (bits != 0),
                          scores.astype(np.float32), NEG_INF)
        order = np.argsort(-scores, kind="stable")[:k]
        kk = len(order)
        out_v[b, :kk] = scores[order]
        out_i[b, :kk] = np.where(scores[order] <= NEG_INF, -1, cand[order])
    return out_v, out_i


def _unpack_words_np(words: np.ndarray, n: int) -> np.ndarray:
    """Numpy twin of :func:`unpack_words_ref` (little-endian bit j of word w
    selects row w*32+j)."""
    words = np.asarray(words, dtype=np.uint32)
    bits = np.unpackbits(words.view(np.uint8), bitorder="little", axis=-1)
    return bits.astype(bool)[..., :n]


def _i8_scores_np(q_i8: np.ndarray, q_scale: np.ndarray, rows_i8: np.ndarray,
                  row_scale: np.ndarray, sq: np.ndarray,
                  metric: str) -> np.ndarray:
    """(q, n) fp32 scores of the int8 scan contract: int32-accumulated dot of
    the codes, the two symmetric scales multiplied back in, and (l2) the
    dequantized-row squared norms subtracted — exact arithmetic for the
    quantized operands (d * 127^2 << 2^31 never rounds in int32)."""
    s32 = q_i8.astype(np.int32) @ rows_i8.astype(np.int32).T
    scores = s32.astype(np.float32) * (
        np.asarray(q_scale, np.float32)[:, None]
        * np.asarray(row_scale, np.float32)[None, :])
    if metric == "l2":
        scores = 2.0 * scores - np.asarray(sq, np.float32)[None, :]
    return scores


def scoped_topk_i8_ref(q_i8: np.ndarray, q_scale: np.ndarray,
                       rows_i8: np.ndarray, row_scale: np.ndarray,
                       sq: np.ndarray, mask: np.ndarray,
                       k: int = 10, metric: str = "ip"
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Unfused numpy oracle for the int8 scan phase of ``scoped_topk_i8``:
    materializes the full (q, n) int32 score matrix, applies the scales,
    masks, full stable sort. ``sq`` is read only for l2 (pass zeros/empty
    padding-to-n for ip/cos)."""
    scores = _i8_scores_np(q_i8, q_scale, rows_i8, row_scale, sq, metric)
    scores = np.where(np.asarray(mask, bool)[None, :], scores, NEG_INF)
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(scores, order, axis=1).astype(np.float32)
    ids = np.where(vals <= NEG_INF, -1, order)
    return vals, ids.astype(np.int32)


def multi_scope_topk_i8_ref(q_i8: np.ndarray, q_scale: np.ndarray,
                            rows_i8: np.ndarray, row_scale: np.ndarray,
                            sq: np.ndarray, mask_words: np.ndarray,
                            scope_ids: np.ndarray,
                            k: int = 10, metric: str = "ip"
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """Unfused numpy oracle for the heterogeneous-batch int8 scan: every
    query row indirects through ``scope_ids`` into the packed (n_scopes,
    ceil(n/32)) uint32 mask matrix, scores as :func:`scoped_topk_i8_ref`."""
    n = rows_i8.shape[0]
    scores = _i8_scores_np(q_i8, q_scale, rows_i8, row_scale, sq, metric)
    masks = _unpack_words_np(mask_words, n)               # (n_scopes, n)
    valid = masks[np.asarray(scope_ids, np.int64)]        # (q, n)
    scores = np.where(valid, scores, NEG_INF)
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(scores, order, axis=1).astype(np.float32)
    ids = np.where(vals <= NEG_INF, -1, order)
    return vals, ids.astype(np.int32)


def _pq_scores_np(lut: np.ndarray, codes: np.ndarray) -> np.ndarray:
    """(q, n) fp32 ADC scores: each row's score is the sum over subspaces of
    the LUT entry its code selects. ``lut`` (q, M, 256) fp32 with the metric
    already folded in (see ``vectordb.quant.PQCodebook.lut`` — for l2 the
    table holds ``2 q.c - |c|^2`` so the sum is the scan's larger-is-better
    l2 identity); ``codes`` (n, M) uint8."""
    lut = np.asarray(lut, dtype=np.float32)
    codes = np.asarray(codes)
    m = codes.shape[1]
    sel = lut[:, np.arange(m)[None, :], codes.astype(np.int64)]  # (q, n, M)
    return sel.sum(axis=2).astype(np.float32)


def scoped_topk_pq_ref(lut: np.ndarray, codes: np.ndarray, mask: np.ndarray,
                       k: int = 10) -> Tuple[np.ndarray, np.ndarray]:
    """Unfused numpy oracle for the PQ/ADC scan phase of ``scoped_topk_pq``:
    full (q, n) ADC score matrix, mask, stable sort. Metric-free — the LUT
    carries it."""
    scores = _pq_scores_np(lut, codes)
    scores = np.where(np.asarray(mask, bool)[None, :], scores, NEG_INF)
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(scores, order, axis=1).astype(np.float32)
    ids = np.where(vals <= NEG_INF, -1, order)
    return vals, ids.astype(np.int32)


def multi_scope_topk_pq_ref(lut: np.ndarray, codes: np.ndarray,
                            mask_words: np.ndarray, scope_ids: np.ndarray,
                            k: int = 10) -> Tuple[np.ndarray, np.ndarray]:
    """Unfused numpy oracle for the heterogeneous-batch ADC scan: every
    query row indirects through ``scope_ids`` into the packed mask matrix,
    scores as :func:`scoped_topk_pq_ref`."""
    n = codes.shape[0]
    scores = _pq_scores_np(lut, codes)
    masks = _unpack_words_np(mask_words, n)               # (n_scopes, n)
    valid = masks[np.asarray(scope_ids, np.int64)]        # (q, n)
    scores = np.where(valid, scores, NEG_INF)
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(scores, order, axis=1).astype(np.float32)
    ids = np.where(vals <= NEG_INF, -1, order)
    return vals, ids.astype(np.int32)


def mask_and_popcount_ref(a: jax.Array, b: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
    words = a & b
    count = jnp.sum(jax.lax.population_count(words).astype(jnp.int32))
    return words, count


def bitmap_patch_ref(masks: jax.Array, delta: jax.Array,
                     ops: jax.Array) -> jax.Array:
    """jnp twin of the batched mask-patch kernel: per-row OR (+1) / AND-NOT
    (-1) / passthrough (0) of one shared delta row."""
    d = delta.reshape(1, -1)
    op = ops.reshape(-1, 1)
    return jnp.where(op > 0, masks | d, jnp.where(op < 0, masks & ~d, masks))


def bitmap_patch_np(masks: np.ndarray, delta: np.ndarray,
                    ops: np.ndarray) -> np.ndarray:
    """Numpy oracle for ``bitmap_patch`` (the mask-cache host fast path)."""
    out = np.array(masks, dtype=np.uint32, copy=True)
    ops = np.asarray(ops).reshape(-1)
    delta = np.asarray(delta, dtype=np.uint32).reshape(-1)
    out[ops > 0] |= delta
    out[ops < 0] &= ~delta
    return out


def flash_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                     length_mask: jax.Array) -> jax.Array:
    """Plain GQA attention for one query token (no flash blocking)."""
    b, h, d = q.shape
    _, kv_h, s, _ = k.shape
    group = h // kv_h
    qg = q.reshape(b, kv_h, group, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scale = 1.0 / float(np.sqrt(d))
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, kf) * scale
    valid = length_mask.astype(bool)[:, None, None, :]
    scores = jnp.where(valid, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    p = jnp.where(valid, p, 0.0)
    out = jnp.einsum("bkgs,bksd->bkgd", p, vf)
    return out.reshape(b, h, d).astype(q.dtype)
