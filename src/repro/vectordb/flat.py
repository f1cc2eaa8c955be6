"""Flat (brute-force) masked top-k executor — exact oracle + baseline.

Two execution plans, chosen by scope selectivity exactly as selective-filter
vector databases do (pre- vs post-filter):

* ``gather``: gather the |C| candidate rows and score only those — optimal for
  selective scopes (|C| << N);
* ``scan``: score all N rows on the MXU-friendly path and mask invalid lanes
  to -inf — optimal for broad scopes, and the shape the Pallas ``scoped_topk``
  kernel implements on TPU.

Both plans additionally come in two *precisions*: the default exact fp32
path, and the int8 scalar-quantized two-phase path (``precision="int8"``):
the int8 scan/gather reads the quarter-size quantized store to select
``rescore_k >= k`` candidates, then :func:`gather_rescore` ranks exactly
those candidates in exact fp32 — so the final scores are always true fp32
scores and the only approximation is which candidates survive phase 1.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import tracing
# the hand-set crossover now lives in costmodel (re-exported here because
# this module owns the decision *rule* that consumes it)
from .costmodel import GATHER_THRESHOLD, model_of
from .quant import int_exact_dot, quantize_rows, resolve_rescore_k
from .store import VectorStore, pack_ids_to_words


# Exact fp32 scoring: on the TPU an fp32 matmul at default precision is a
# single bf16 pass, so every exact path asks for full fp32 explicitly.
EXACT = jax.lax.Precision.HIGHEST


def exact_scores(queries: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """(q, d) x (n, d) -> (q, n) fp32 inner products at full fp32 precision
    — the scoring expression of every exact scan (flat and sharded)."""
    return jnp.matmul(queries, rows.T, precision=EXACT)


def bucket(n: int, floor: int = 8) -> int:
    """Padded size of a variable launch axis (queries in a group, scopes in
    a batch, gathered candidates): the next power of two >= ``max(n,
    floor)``. Serving batches vary on every one of these axes, and each new
    shape is a compile, so launches pad to a handful of sizes."""
    return max(floor, 1 << max(n - 1, 0).bit_length())


def pad_rows(x, n: int, value=0) -> np.ndarray:
    """``x`` with its leading axis padded to ``n`` rows of ``value``."""
    x = np.asarray(x)
    pad = [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, pad, constant_values=value)


def to_device(*arrays) -> Tuple[jnp.ndarray, ...]:
    """Host arrays handed to the device, in one ``dsq.h2d`` span whose bytes
    count toward the batch's ``h2d_bytes``."""
    with tracing.span("dsq.h2d"):
        tracing.count_h2d(sum(a.nbytes for a in arrays))
        return tuple(jnp.asarray(a) for a in arrays)


def to_host(*arrays) -> Tuple[np.ndarray, ...]:
    """Device results on the host, in one ``dsq.fetch`` span: the time the
    thread waits for the device."""
    with tracing.span("dsq.fetch"):
        return tuple(np.asarray(a) for a in arrays)


def choose_plan(m: int, n: int, k: int,
                threshold: float = GATHER_THRESHOLD) -> str:
    """THE gather/scan decision rule. ``FlatExecutor.search``, the
    ``BatchPlanner`` and ``ShardedExecutor.search`` all delegate here — the
    batch==loop and sharded==flat bit-identity contracts require every path
    to pick the same plan for the same scope. Calibrated deployments pass
    ``threshold=model.gather_threshold(n, k)``; the rule itself never
    changes, only the measured crossover."""
    return "gather" if m <= max(k, threshold * n) else "scan"


def pad_topk(scores: np.ndarray, ids: np.ndarray,
             k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Right-pad (q, kk) results to (q, k) with the -inf / -1 sentinels."""
    kk = scores.shape[1]
    if kk >= k:
        return scores, np.asarray(ids, dtype=np.int64)
    q = scores.shape[0]
    pad_s = np.full((q, k - kk), -np.inf, np.float32)
    pad_i = np.full((q, k - kk), -1, np.int64)
    return (np.concatenate([scores, pad_s], axis=1),
            np.concatenate([np.asarray(ids, np.int64), pad_i], axis=1))


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _scan_topk(queries: jnp.ndarray, rows: jnp.ndarray, sq: jnp.ndarray,
               words: jnp.ndarray,
               k: int, metric: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-scope scan over a packed uint32 word mask (ceil(n/32) words,
    unpacked in-register — 32x less host->device mask traffic than the old
    dense bool hand-off). ``sq`` is the store's cached device squared norms,
    read only on the (trace-time static) l2 branch — pass a zero-length
    array for ip/cos."""
    from ..kernels.ref import unpack_words_ref
    n = rows.shape[0]
    if metric in ("ip", "cos"):
        scores = exact_scores(queries, rows)
    else:  # l2: argmax of -(||q||^2 - 2 q.x + ||x||^2) == argmax(2 q.x - ||x||^2)
        scores = 2.0 * exact_scores(queries, rows) - sq[None, :]
    mask = unpack_words_ref(words, n)                       # (n,)
    scores = jnp.where(mask[None, :], scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _multi_scan_topk(queries: jnp.ndarray, rows: jnp.ndarray,
                     sq: jnp.ndarray, mask_words: jnp.ndarray,
                     scope_ids: jnp.ndarray,
                     k: int, metric: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Heterogeneous-batch scan: one launch ranks every scan-plan request in
    the batch. Each query row indirects through ``scope_ids`` into a packed
    (n_scopes, ceil(n/32)) uint32 mask matrix, unpacked in-register on
    device (the jnp twin of the Pallas ``multi_scope_topk`` kernel). ``sq``
    is the cached device squared-norm vector, l2-only like in
    :func:`_scan_topk` (both paths must share it for batch==loop
    bit-identity)."""
    from ..kernels.ref import unpack_words_ref
    n = rows.shape[0]
    if metric in ("ip", "cos"):
        scores = exact_scores(queries, rows)
    else:
        scores = 2.0 * exact_scores(queries, rows) - sq[None, :]
    masks = unpack_words_ref(mask_words, n)                 # (n_scopes, n)
    valid = jnp.take(masks, scope_ids, axis=0)              # (B, n)
    scores = jnp.where(valid, scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


# (q, d) x (n, d) int8 code dot as fp32 — see quant.int_exact_dot, the
# single shared definition every int8 jnp twin scores through
_int_exact_dot = int_exact_dot


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _scan_topk_i8(q_i8: jnp.ndarray, q_scale: jnp.ndarray,
                  rows_i8: jnp.ndarray, row_scale: jnp.ndarray,
                  sq: jnp.ndarray, words: jnp.ndarray,
                  k: int, metric: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """jnp twin of the Pallas ``scoped_topk_i8`` kernel: int8-code scan of
    the quantized store, symmetric scales applied after accumulation, packed
    word mask. ``sq`` holds the *dequantized-row* squared norms (l2 only)."""
    from ..kernels.ref import unpack_words_ref
    n = rows_i8.shape[0]
    scores = _int_exact_dot(q_i8, rows_i8) * (
        q_scale[:, None] * row_scale[None, :])
    if metric == "l2":
        scores = 2.0 * scores - sq[None, :]
    mask = unpack_words_ref(words, n)
    scores = jnp.where(mask[None, :], scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _multi_scan_topk_i8(q_i8: jnp.ndarray, q_scale: jnp.ndarray,
                        rows_i8: jnp.ndarray, row_scale: jnp.ndarray,
                        sq: jnp.ndarray, mask_words: jnp.ndarray,
                        scope_ids: jnp.ndarray,
                        k: int, metric: str
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """jnp twin of the Pallas ``multi_scope_topk_i8`` kernel (heterogeneous
    scope batch over the int8 store)."""
    from ..kernels.ref import unpack_words_ref
    n = rows_i8.shape[0]
    scores = _int_exact_dot(q_i8, rows_i8) * (
        q_scale[:, None] * row_scale[None, :])
    if metric == "l2":
        scores = 2.0 * scores - sq[None, :]
    masks = unpack_words_ref(mask_words, n)
    valid = jnp.take(masks, scope_ids, axis=0)
    scores = jnp.where(valid, scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _gather_topk_i8(q_i8: jnp.ndarray, q_scale: jnp.ndarray,
                    cand_i8: jnp.ndarray, cand_scale: jnp.ndarray,
                    cand_sq: jnp.ndarray, valid: jnp.ndarray,
                    k: int, metric: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int8 phase of the gather plan: score only the |C| candidate codes
    (``valid`` False on padding lanes)."""
    scores = _int_exact_dot(q_i8, cand_i8) * (
        q_scale[:, None] * cand_scale[None, :])
    if metric == "l2":
        scores = 2.0 * scores - cand_sq[None, :]
    return jax.lax.top_k(jnp.where(valid[None, :], scores, -jnp.inf), k)


def _adc_scores(lut: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """(B, n) PQ/ADC scores — THE shared scoring primitive of every PQ jnp
    twin (flat scan/gather, IVF tile scoring via its gathered variant, the
    sharded local scan), mirroring ``int_exact_dot``'s role for int8. One
    256-lane ``take`` per subspace accumulated into (B, n), so no (B, n, M)
    intermediate ever materializes — the shape XLA:CPU executes fastest (the
    Pallas kernel fuses the same gather in VMEM). Metric-free: the LUT
    folds it in (see quant.PQCodebook.lut)."""
    c = codes.astype(jnp.int32)
    scores = jnp.take(lut[:, 0, :], c[:, 0], axis=1)
    for m in range(1, codes.shape[1]):
        scores = scores + jnp.take(lut[:, m, :], c[:, m], axis=1)
    return scores


@functools.partial(jax.jit, static_argnames=("k",))
def _scan_topk_pq(lut: jnp.ndarray, codes: jnp.ndarray, words: jnp.ndarray,
                  k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """jnp twin of the Pallas ``scoped_topk_pq`` kernel: ADC scan of the
    uint8 code store through the per-query LUT, packed word mask."""
    from ..kernels.ref import unpack_words_ref
    n = codes.shape[0]
    scores = _adc_scores(lut, codes)
    mask = unpack_words_ref(words, n)
    scores = jnp.where(mask[None, :], scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _multi_scan_topk_pq(lut: jnp.ndarray, codes: jnp.ndarray,
                        mask_words: jnp.ndarray, scope_ids: jnp.ndarray,
                        k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """jnp twin of the Pallas ``multi_scope_topk_pq`` kernel (heterogeneous
    scope batch over the PQ code store)."""
    from ..kernels.ref import unpack_words_ref
    n = codes.shape[0]
    scores = _adc_scores(lut, codes)
    masks = unpack_words_ref(mask_words, n)
    valid = jnp.take(masks, scope_ids, axis=0)
    scores = jnp.where(valid, scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _gather_topk_pq(lut: jnp.ndarray, cand_codes: jnp.ndarray,
                    valid: jnp.ndarray,
                    k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ADC phase of the gather plan: score only the |C| candidate codes
    (``valid`` False on padding lanes)."""
    scores = _adc_scores(lut, cand_codes)
    return jax.lax.top_k(jnp.where(valid[None, :], scores, -jnp.inf), k)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _rescore_topk(queries: jnp.ndarray, cand_rows: jnp.ndarray,
                  valid: jnp.ndarray,
                  k: int, metric: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Phase 2 of the int8 plan: exact fp32 scores of per-query gathered
    candidate rows (B, R, d), invalid (-1 padded) lanes masked to -inf."""
    scores = jax.lax.dot_general(
        cand_rows, queries, (((2,), (1,)), ((0,), (0,))),
        precision=EXACT, preferred_element_type=jnp.float32)  # (B, R)
    if metric == "l2":
        scores = 2.0 * scores - jnp.sum(cand_rows * cand_rows, axis=-1)
    scores = jnp.where(valid, scores, -jnp.inf)
    return jax.lax.top_k(scores, k)


def gather_rescore(store: VectorStore, queries: np.ndarray,
                   cand_ids: np.ndarray, k: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact fp32 gather-rescore of int8-phase candidates — the shared back
    half of every two-phase executor path (flat scan/gather, IVF, sharded
    post-merge). ``cand_ids`` is (B, R) int64 store ids with -1 padding;
    returns (scores, ids) both (B, k), -1/-inf padded."""
    queries = np.atleast_2d(np.asarray(queries, dtype=np.float32))
    cand_ids = np.asarray(cand_ids, dtype=np.int64)
    # block-padding rows surfaced by stray mask tail bits are not real rows
    cand_ids = np.where(cand_ids < len(store), cand_ids, -1)
    if store.tiered_active():
        # tiered store: exact rows live in host RAM; every valid candidate
        # outside the device-pinned hot set is a host->device fetch
        fetch = cand_ids >= 0
        pm = store.pinned_mask()
        if pm is not None:
            fetch = fetch & ~pm[np.maximum(cand_ids, 0)]
        store.rescore_fetch_bytes += (int(np.count_nonzero(fetch))
                                      * store.dim * 4)
    kk = min(k, cand_ids.shape[1])
    b, bp = len(queries), bucket(len(queries))
    with tracing.span("dsq.gather.rows"):
        rows = pad_rows(store.fetch_rows(np.maximum(cand_ids, 0)), bp)
    q, rows, valid = to_device(pad_rows(queries, bp), rows,
                               pad_rows(cand_ids >= 0, bp))
    with tracing.span("dsq.launch"):
        vals, loc = _rescore_topk(q, rows, valid, kk, store.metric)
    vals, loc = to_host(vals, loc)
    vals = np.asarray(vals[:b], np.float32)
    ids = np.take_along_axis(cand_ids, loc[:b].astype(np.int64), axis=1)
    ids[~np.isfinite(vals)] = -1
    return pad_topk(vals, ids, k)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _gather_topk(queries: jnp.ndarray, cand_rows: jnp.ndarray,
                 valid: jnp.ndarray,
                 k: int, metric: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact fp32 gather plan over candidate rows (``valid`` False on
    padding lanes)."""
    scores = exact_scores(queries, cand_rows)
    if metric == "l2":
        scores = 2.0 * scores - jnp.sum(
            cand_rows * cand_rows, axis=-1)[None, :]
    return jax.lax.top_k(jnp.where(valid[None, :], scores, -jnp.inf), k)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _gather_topk_ids(queries: jnp.ndarray, rows: jnp.ndarray,
                     ids: jnp.ndarray, m: jnp.ndarray,
                     k: int, metric: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`_gather_topk` over rows taken by id from the device-resident
    store (``ids`` int32, padded past ``m`` with an in-bounds id whose lanes
    are masked). Only the ids are padded: padding ``rows`` would copy the
    whole store."""
    cand_rows = jnp.take(rows, ids, axis=0, mode="clip")
    valid = jnp.arange(ids.shape[0]) < m
    return _gather_topk(queries, cand_rows, valid, k=k, metric=metric)


class FlatExecutor:
    name = "flat"

    def __init__(self, store: VectorStore, device_gather: bool = True):
        self.store = store
        # the gather plan takes rows by id from the store's device copy
        # unless the rows are not on one device: a tiered store keeps them
        # in host RAM, and the sharded tier's twin (``device_gather=False``)
        # serves a store that need not fit one device
        self.device_gather = device_gather

    def _sq(self) -> jnp.ndarray:
        """Cached device squared norms for the l2 scan — an empty array for
        ip/cos, so the O(n) transfer is never paid on the branch that does
        not read it (the sq term is trace-time static)."""
        return (self.store.device_sq_norms()
                if self.store.metric == "l2" else jnp.zeros(0, jnp.float32))

    def _q_sq(self) -> jnp.ndarray:
        """int8-tier counterpart of :meth:`_sq` (dequantized-row norms)."""
        return (self.store.device_q_sq_norms()
                if self.store.metric == "l2" else jnp.zeros(0, jnp.float32))

    def search(self, queries: np.ndarray, k: int,
               candidate_ids: Optional[np.ndarray] = None,
               plan: Optional[str] = None, precision: str = "fp32",
               rescore_k: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (scores, ids), both (q, k); ids == -1 past the scope size.
        ``precision="int8"`` runs the two-phase plan (int8 scan/gather keeps
        ``rescore_k`` candidates, exact fp32 rescore ranks the final k);
        the default fp32 path is untouched by the knob."""
        queries = np.atleast_2d(np.asarray(queries, dtype=np.float32))
        n = len(self.store)
        if candidate_ids is None:
            candidate_ids = np.arange(n, dtype=np.uint32)
        m = len(candidate_ids)
        if m == 0:
            q = queries.shape[0]
            return (np.full((q, k), -np.inf, np.float32),
                    np.full((q, k), -1, np.int64))
        if plan is None:
            plan = choose_plan(
                m, n, k, model_of(self.store).gather_threshold(n, k))
        if precision == "int8":
            r = resolve_rescore_k(k, rescore_k, m)
            # a gather scope the rescore window covers entirely gains nothing
            # from an int8 phase — the exact fp32 gather IS the planned
            # precision for it (the same rule BatchPlanner applies per group)
            if not (plan == "gather" and m <= r):
                return self._search_int8(queries, k, candidate_ids, plan, r)
        if precision == "pq":
            r = resolve_rescore_k(k, rescore_k, m)
            # same window rule as int8: tiny gathers stay exact fp32
            if not (plan == "gather" and m <= r):
                return self._search_pq(queries, k, candidate_ids, plan, r)
        kk = min(k, m)
        b = len(queries)
        qp = pad_rows(queries, bucket(b))
        if plan == "gather":
            mp = bucket(m)
            on_device = self.device_gather and not self.store.tiered_active()
            tracing.count_gather_rows(m, on_device)
            if on_device:
                with tracing.span("dsq.gather.rows"):
                    # the device take clamps; an id past the store must
                    # fail as the host fancy-index does
                    if int(np.max(candidate_ids)) >= n:
                        raise IndexError("candidate id past the store")
                    ids = np.zeros(mp, np.int32)
                    ids[:m] = candidate_ids
                qp, ids, m_d = to_device(qp, ids, np.int32(m))
                with tracing.span("dsq.launch"):
                    scores, local = _gather_topk_ids(
                        qp, self.store.device_vectors(), ids, m_d, kk,
                        self.store.metric)
            else:
                with tracing.span("dsq.gather.rows"):
                    rows = pad_rows(self.store.vectors[candidate_ids], mp)
                qp, rows, valid = to_device(qp, rows, np.arange(mp) < m)
                with tracing.span("dsq.launch"):
                    scores, local = _gather_topk(qp, rows, valid, kk,
                                                 self.store.metric)
            scores, local = to_host(scores, local)
            ids = candidate_ids[local[:b]]
        else:
            qp, words = to_device(qp, pack_ids_to_words(candidate_ids, n))
            with tracing.span("dsq.launch"):
                scores, ids = _scan_topk(
                    qp, self.store.device_vectors(), self._sq(), words, kk,
                    self.store.metric)
            scores, ids = to_host(scores, ids)
            ids = ids[:b]
        return pad_topk(scores[:b], ids, k)

    def _search_int8(self, queries: np.ndarray, k: int,
                     candidate_ids: np.ndarray, plan: str, r: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Two-phase int8 path of :meth:`search` (r = effective rescore_k)."""
        n, b = len(self.store), len(queries)
        q_i8, q_s = quantize_rows(pad_rows(queries, bucket(b)))
        if plan == "gather":
            m = len(candidate_ids)
            mp = bucket(m)
            with tracing.span("dsq.gather.rows"):
                cand_sq = (pad_rows(self.store.q_sq_norms()[candidate_ids],
                                    mp)
                           if self.store.metric == "l2"
                           else np.zeros(0, np.float32))
                rows = pad_rows(self.store.q_vectors[candidate_ids], mp)
                scales = pad_rows(self.store.q_scales[candidate_ids], mp)
            args = to_device(q_i8, q_s, rows, scales, cand_sq,
                             np.arange(mp) < m)
            with tracing.span("dsq.launch"):
                _, local = _gather_topk_i8(*args, r, self.store.metric)
            local, = to_host(local)
            cand = np.asarray(candidate_ids, np.int64)[local[:b]]
        else:
            q_i8, q_s, words = to_device(
                q_i8, q_s, pack_ids_to_words(candidate_ids, n))
            with tracing.span("dsq.launch"):
                vals, cand = _scan_topk_i8(
                    q_i8, q_s, self.store.device_q_vectors(),
                    self.store.device_q_scales(), self._q_sq(), words,
                    min(r, n), self.store.metric)
            vals, cand = to_host(vals, cand)
            cand = cand[:b].astype(np.int64)
            # top_k hands exhausted (-inf) lanes arbitrary column ids — they
            # are out-of-scope rows and must not reach the rescore
            cand[~np.isfinite(vals[:b])] = -1
        return gather_rescore(self.store, queries, cand, k)

    def _search_pq(self, queries: np.ndarray, k: int,
                   candidate_ids: np.ndarray, plan: str, r: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Two-phase PQ path of :meth:`search`: ADC scan/gather over the
        uint8 codes selects ``r`` candidates, exact fp32 rescore ranks k."""
        n, b = len(self.store), len(queries)
        lut = self.store.pq_lut(pad_rows(queries, bucket(b)))
        if plan == "gather":
            m = len(candidate_ids)
            mp = bucket(m)
            with tracing.span("dsq.gather.rows"):
                codes = pad_rows(self.store.pq_codes[candidate_ids], mp)
            args = to_device(lut, codes, np.arange(mp) < m)
            with tracing.span("dsq.launch"):
                _, local = _gather_topk_pq(*args, r)
            local, = to_host(local)
            cand = np.asarray(candidate_ids, np.int64)[local[:b]]
        else:
            lut, words = to_device(lut, pack_ids_to_words(candidate_ids, n))
            with tracing.span("dsq.launch"):
                vals, cand = _scan_topk_pq(lut, self.store.device_pq_codes(),
                                           words, min(r, n))
            vals, cand = to_host(vals, cand)
            cand = cand[:b].astype(np.int64)
            # exhausted (-inf) lanes carry arbitrary top_k column ids — out
            # of scope, keep them away from the rescore
            cand[~np.isfinite(vals[:b])] = -1
        return gather_rescore(self.store, queries, cand, k)

    def search_multi(self, queries: np.ndarray, mask_words: np.ndarray,
                     scope_ids: np.ndarray, k: int,
                     use_pallas: bool = False, precision: str = "fp32",
                     rescore_k: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """One launch for a heterogeneous scan-plan batch: queries (B, d),
        packed masks (n_scopes, ceil(n/32)), per-query scope row ids (B,).
        Returns (scores, ids) both (B, k), ids int64, -1 where the scope had
        no candidate. The default jnp twin of the Pallas ``multi_scope_topk``
        keeps results bit-identical to the per-request scan path; pass
        ``use_pallas=True`` on real TPUs for the fused kernel (same top-k
        set, but tie order/low score bits may differ from the unfused
        jax.lax.top_k). ``precision="int8"`` swaps
        phase 1 to the quantized-store scan (``multi_scope_topk_i8`` fused,
        or its jnp twin) and finishes with the shared exact fp32 rescore.
        Queries and mask rows pad to :func:`bucket` sizes, so batches of
        any shape reuse a few compiled launches."""
        from ..kernels import ops as kops
        queries = np.atleast_2d(np.asarray(queries, dtype=np.float32))
        b = len(queries)
        qp = pad_rows(queries, bucket(b))
        words = pad_rows(np.asarray(mask_words, np.uint32),
                         bucket(len(mask_words)))
        sids = pad_rows(np.asarray(scope_ids, np.int32), bucket(b))
        if precision in ("int8", "pq"):
            r = resolve_rescore_k(k, rescore_k, len(self.store))
            phase1 = (self._scan_multi_int8 if precision == "int8"
                      else self._scan_multi_pq)
            vals, cand = phase1(qp, words, sids, r, use_pallas)
            vals, cand = to_host(vals, cand)
            cand = cand[:b].astype(np.int64)
            # exhausted (-inf) lanes carry arbitrary top_k column ids (the
            # fused kernels already yield -1); mask them out of the rescore
            cand[~np.isfinite(vals[:b])] = -1
            return gather_rescore(self.store, queries, cand, k)
        qp, words, sids = to_device(qp, words, sids)
        with tracing.span("dsq.launch"):
            if use_pallas:
                scores, ids = kops.multi_scope_topk(
                    qp, self.store.device_vectors(), words, sids, k=k,
                    metric=self.store.metric, sq=self._sq())
            else:
                scores, ids = _multi_scan_topk(
                    qp, self.store.device_vectors(), self._sq(), words, sids,
                    k, self.store.metric)
        scores, ids = to_host(scores, ids)
        scores = scores[:b]
        ids = ids[:b].astype(np.int64)
        ids[~np.isfinite(scores)] = -1
        return scores, ids

    def _scan_multi_int8(self, queries, words, sids, r, use_pallas):
        """int8 scan phase of :meth:`search_multi`: (vals, cand) (B, r)."""
        from ..kernels import ops as kops
        q_i8, q_s, words, sids = to_device(*quantize_rows(queries), words,
                                           sids)
        with tracing.span("dsq.launch"):
            if use_pallas:
                # the kernel streams the sq tile unconditionally; hand it a
                # device zeros vector on the metrics that never read it
                sq = (self.store.device_q_sq_norms()
                      if self.store.metric == "l2"
                      else jnp.zeros(len(self.store), jnp.float32))
                return kops.multi_scope_topk_i8(
                    q_i8, q_s, self.store.device_q_vectors(),
                    self.store.device_q_scales(), sq, words, sids, k=r,
                    metric=self.store.metric)
            return _multi_scan_topk_i8(
                q_i8, q_s, self.store.device_q_vectors(),
                self.store.device_q_scales(), self._q_sq(), words, sids, r,
                self.store.metric)

    def _scan_multi_pq(self, queries, words, sids, r, use_pallas):
        """PQ/ADC scan phase of :meth:`search_multi`: (vals, cand) (B, r)."""
        from ..kernels import ops as kops
        lut, words, sids = to_device(self.store.pq_lut(queries), words, sids)
        with tracing.span("dsq.launch"):
            if use_pallas:
                return kops.multi_scope_topk_pq(
                    lut, self.store.device_pq_codes(), words, sids, k=r)
            return _multi_scan_topk_pq(lut, self.store.device_pq_codes(),
                                       words, sids, r)
