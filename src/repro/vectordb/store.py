"""Vector store: host-resident rows + lazily-cached device array.

Entry ids are row indices (uint32), the same ids kept in the scope indexes'
RoaringBitmaps — the hand-off between the directory layer and the ANN executor
is therefore a pure id-set/bitmask, per the paper's execution model (§II-A).

:class:`ShardedStoreView` is the multi-device mirror of that contract: the
same append-only rows, kept row-sharded across a device mesh with incremental
(amortized-doubling) re-shard on ingest growth, plus the packed alive mask the
sharded scan ANDs in-register.
"""
from __future__ import annotations

import functools
import time
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import faults
from .quant import PQCodebook, default_pq_m, quantize_rows

METRICS = ("ip", "l2", "cos")


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(db: jnp.ndarray, rows: jnp.ndarray,
                  start) -> jnp.ndarray:
    """In-place row scatter (the old buffer is donated, so XLA updates it
    without an O(capacity) copy — the point of the incremental sync).
    Callers pad ``rows`` to power-of-two sizes so the jit cache stays
    bounded at log2(capacity) traces instead of one per ingest size."""
    return jax.lax.dynamic_update_slice(db, rows, (start, 0))


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_words(words: jnp.ndarray, seg: jnp.ndarray, start) -> jnp.ndarray:
    """In-place word-range scatter for the packed alive mask (same donation
    and power-of-two-width caveats as :func:`_scatter_rows`)."""
    return jax.lax.dynamic_update_slice(words, seg, (start,))


def _pow2_at_most(n: int, cap: int) -> int:
    out = 1
    while out < n:
        out *= 2
    return min(out, cap)


def pack_ids_to_words(candidate_ids: Optional[np.ndarray],
                      n: int) -> np.ndarray:
    """Pack an id array into ``ceil(n/32)`` little-endian uint32 mask words
    (the same layout as ``RoaringBitmap.to_words``). ``None`` packs the full
    ``[0, n)`` range; out-of-range ids are dropped."""
    n_words = max((n + 31) // 32, 1)
    if candidate_ids is None:
        words = np.full(n_words, 0xFFFFFFFF, dtype=np.uint32)
        if n % 32:
            words[-1] = np.uint32((1 << (n % 32)) - 1)
        if n == 0:
            words[:] = 0
        return words
    ids = np.asarray(candidate_ids, dtype=np.int64)
    ids = ids[(ids >= 0) & (ids < n)]
    if len(ids) * 16 > n:
        # broad scope: dense mask + packbits beats the per-id scattered
        # bitwise_or.at
        mask = np.zeros(n_words * 32, dtype=bool)
        mask[ids] = True
        return np.packbits(mask, bitorder="little").view(np.uint32)
    words = np.zeros(n_words, dtype=np.uint32)
    np.bitwise_or.at(words, ids >> 5,
                     np.uint32(1) << (ids & 31).astype(np.uint32))
    return words


class VectorStore:
    def __init__(self, dim: int, metric: str = "ip", capacity: int = 1024,
                 pq_m: Optional[int] = None):
        if metric not in METRICS:
            raise ValueError(f"metric {metric!r} not in {METRICS}")
        self.dim = dim
        self.metric = metric
        # attached cost model (vectordb.costmodel.CostModel) — None means
        # the heuristic constants; every decision site reads it through
        # costmodel.model_of(store), so one attachment calibrates the whole
        # executor matrix consistently (bit-identity across paths)
        self.cost_model = None
        self._rows = np.zeros((capacity, dim), dtype=np.float32)
        self._n = 0
        self._device_cache: Optional[jnp.ndarray] = None
        self._norms_cache: Optional[np.ndarray] = None
        self._device_norms: Optional[jnp.ndarray] = None
        # int8 scalar-quantized tier: per-row codes + scale, maintained
        # incrementally alongside the fp32 rows through a lazy watermark —
        # rows [0, _q_n) are quantized, and any quantized-tier accessor
        # catches the mirror up to _n first (so a pure-fp32 workload never
        # pays the quantization, and once the tier is in use each ingest
        # batch is quantized exactly once). Tombstones need no mirror:
        # deleted rows are masked out by the same packed alive/scope words
        # both precisions AND in. Device mirrors are lazily cached like the
        # fp32 ones.
        self._q_rows: Optional[np.ndarray] = None
        self._q_scale: Optional[np.ndarray] = None
        self._q_n = 0
        self._device_q: Optional[jnp.ndarray] = None
        self._device_q_scale: Optional[jnp.ndarray] = None
        self._q_norms_cache: Optional[np.ndarray] = None
        self._device_q_norms: Optional[jnp.ndarray] = None
        # PQ/ADC tier: one uint8 code per subspace against a codebook that
        # trains once on the rows present at first use and is then frozen
        # (see quant.PQCodebook), so codes for already-ingested rows never
        # change. Maintained through the same lazy watermark as the int8
        # mirror: rows [0, _pq_n) are encoded, accessors catch up first.
        self._pq_m = pq_m
        self._pq: Optional[PQCodebook] = None
        self._pq_codes: Optional[np.ndarray] = None
        self._pq_n = 0
        self._device_pq: Optional[jnp.ndarray] = None
        # Tiered storage: when a device byte budget is configured and the
        # fp32 rows outgrow it, fp32 rows demote to host RAM — only the PQ
        # codes (plus any hot-pinned fp32 rows) stay device-resident, and
        # the exact rows are fetched per batch for the gather_rescore
        # window. The fetch counters are cumulative; per-batch accounting
        # snapshots the delta.
        self._device_budget: Optional[int] = None
        self._pinned: Optional[np.ndarray] = None
        self.rescore_fetch_bytes = 0
        self.rescore_fetch_rows = 0
        # Host-fetch fault handling: transient faults at the
        # ``store.host_fetch`` seam are retried with exponential backoff
        # (bounded), counted here and surfaced through BatchAccounting.
        self.host_fetch_retries = 0
        self.host_fetch_failures = 0
        # Tombstones: rows are append-only, so a delete marks the id dead
        # here and every executor consults the alive mask at query time
        # (scoped searches drop deleted ids via the directory layer already;
        # this covers unscoped ivf/pg probes whose partition lists / graph
        # nodes still reference the row).
        self._deleted = np.zeros(capacity, dtype=bool)
        self._n_deleted = 0
        self._alive_words: Optional[np.ndarray] = None
        # Tombstone id log: incremental consumers (the sharded view's alive
        # mask, the maintenance manager) patch only the words these ids
        # touch instead of rebuilding/re-uploading the whole mask per
        # delete. The log is *bounded*: consumers register a cursor and the
        # prefix every registered cursor has passed is dropped
        # (``_deleted_log_base`` tracks the absolute index of element 0, so
        # cursors survive truncation without rebasing each consumer). With
        # no registered consumers the log is kept whole — legacy readers of
        # ``deleted_log`` see the full history.
        self._deleted_log: list = []
        self._deleted_log_base = 0
        self._log_cursors: dict = {}      # consumer handle -> absolute cursor
        self._next_log_consumer = 0
        # bumped by every completed compact() — the maintenance journal's
        # idempotence probe (was the crashed compaction's swap reached?)
        self.compact_gen = 0

    def __len__(self) -> int:
        return self._n

    @property
    def vectors(self) -> np.ndarray:
        return self._rows[: self._n]

    def add(self, vectors: np.ndarray) -> np.ndarray:
        """Append rows; returns assigned entry ids."""
        vectors = np.atleast_2d(np.asarray(vectors, dtype=np.float32))
        if vectors.shape[1] != self.dim:
            raise ValueError(f"dim mismatch: {vectors.shape[1]} != {self.dim}")
        n_new = vectors.shape[0]
        while self._n + n_new > self._rows.shape[0]:
            grown = np.zeros((max(2 * self._rows.shape[0], self._n + n_new),
                              self.dim), dtype=np.float32)
            grown[: self._n] = self._rows[: self._n]
            self._rows = grown
        if self._n + n_new > self._deleted.shape[0]:
            grown_d = np.zeros(self._rows.shape[0], dtype=bool)
            grown_d[: self._n] = self._deleted[: self._n]
            self._deleted = grown_d
        if self.metric == "cos":
            norms = np.linalg.norm(vectors, axis=1, keepdims=True)
            vectors = vectors / np.maximum(norms, 1e-12)
        self._rows[self._n: self._n + n_new] = vectors
        ids = np.arange(self._n, self._n + n_new, dtype=np.uint32)
        self._n += n_new
        self._device_cache = None
        self._norms_cache = None
        self._alive_words = None
        return ids

    # ----------------------------------------------------------- tombstones
    def mark_deleted(self, ids) -> None:
        """Tombstone rows (append-only store; the rows stay but every
        executor masks them out of results)."""
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        ids = ids[(ids >= 0) & (ids < self._n)]
        fresh = ids[~self._deleted[ids]]
        if len(fresh) == 0:
            return
        self._deleted[fresh] = True
        self._n_deleted += len(fresh)
        self._deleted_log.extend(int(i) for i in fresh)
        self._alive_words = None

    @property
    def n_deleted(self) -> int:
        return self._n_deleted

    @property
    def deleted_log(self) -> list:
        """Tombstoned ids (in mark order) not yet truncated; prefer the
        cursor API (:meth:`register_log_consumer`) which bounds the log."""
        return self._deleted_log

    @property
    def deleted_log_end(self) -> int:
        """Absolute length of the tombstone history (survives truncation)."""
        return self._deleted_log_base + len(self._deleted_log)

    def register_log_consumer(self) -> int:
        """Register an incremental tombstone-log consumer. The returned
        handle's cursor starts at the current end (a new consumer builds
        its first snapshot from authoritative store state, then follows the
        log). Registration is what lets the store drop consumed history."""
        h = self._next_log_consumer
        self._next_log_consumer += 1
        self._log_cursors[h] = self.deleted_log_end
        return h

    def unregister_log_consumer(self, handle: int) -> None:
        self._log_cursors.pop(handle, None)
        self._truncate_deleted_log()

    def log_consumer_reset(self, handle: int) -> None:
        """Skip the handle to the log end without reading (the consumer just
        rebuilt from scratch, e.g. a capacity re-shard)."""
        self._log_cursors[handle] = self.deleted_log_end
        self._truncate_deleted_log()

    def consume_deleted_log(self, handle: int) -> list:
        """Tombstone ids appended since this handle's cursor; advances the
        cursor to the end and drops any prefix every consumer has passed."""
        start = max(0, self._log_cursors[handle] - self._deleted_log_base)
        out = self._deleted_log[start:]
        self._log_cursors[handle] = self.deleted_log_end
        self._truncate_deleted_log()
        return out

    def _truncate_deleted_log(self) -> None:
        if not self._log_cursors:
            return
        low = min(self._log_cursors.values())
        drop = low - self._deleted_log_base
        if drop > 0:
            del self._deleted_log[:drop]
            self._deleted_log_base = low

    def deleted_mask(self) -> np.ndarray:
        return self._deleted[: self._n]

    def alive_bool(self) -> Optional[np.ndarray]:
        """(n,) bool alive mask, or None when nothing is deleted (the common
        case — callers skip the AND entirely)."""
        if self._n_deleted == 0:
            return None
        return ~self._deleted[: self._n]

    def alive_words(self) -> Optional[np.ndarray]:
        """Packed uint32 alive mask, ceil(n/32) words, or None when nothing
        is deleted. Cached until the next add/mark_deleted."""
        if self._n_deleted == 0:
            return None
        if (self._alive_words is None
                or self._alive_words.shape[0] != (self._n + 31) // 32):
            padded = np.zeros(((self._n + 31) // 32) * 32, dtype=bool)
            padded[: self._n] = ~self._deleted[: self._n]
            self._alive_words = np.packbits(
                padded, bitorder="little").view(np.uint32)
        return self._alive_words

    # ----------------------------------------------------------- compaction
    def compact(self) -> Optional[np.ndarray]:
        """Reclaim tombstoned rows: slide every alive row down (order
        preserved), clear the tombstone set, and re-pack the int8/PQ code
        slabs for the compacted id space (codes are copied, never
        re-encoded — the quantized mirrors stay bit-identical for surviving
        rows; the frozen PQ codebook is untouched).

        Returns the id remap ``mapping[old_id] -> new_id`` (int64, -1 for
        reclaimed rows), or ``None`` when there was nothing to reclaim. The
        caller owns propagating the remap to every id-keyed structure
        (scope indexes, ANN lists/graphs, mask caches, sharded mirrors) —
        see ``maintenance.MaintenanceManager``."""
        if self._n_deleted == 0:
            return None
        old_n = self._n
        alive = ~self._deleted[:old_n]
        new_n = int(np.count_nonzero(alive))
        mapping = np.full(old_n, -1, dtype=np.int64)
        mapping[alive] = np.arange(new_n, dtype=np.int64)
        self._rows[:new_n] = self._rows[:old_n][alive]
        # int8 mirror: compact the encoded prefix; the watermark moves to
        # however many of those encoded rows survived (order-preserving, so
        # the encoded prefix stays a prefix)
        if self._q_rows is not None:
            q_n = min(self._q_n, old_n)
            keep = alive[:q_n]
            new_q = int(np.count_nonzero(keep))
            self._q_rows[:new_q] = self._q_rows[:q_n][keep]
            self._q_scale[:new_q] = self._q_scale[:q_n][keep]
            self._q_n = new_q
        if self._pq_codes is not None:
            pq_n = min(self._pq_n, old_n)
            keep = alive[:pq_n]
            new_pq = int(np.count_nonzero(keep))
            self._pq_codes[:new_pq] = self._pq_codes[:pq_n][keep]
            self._pq_n = new_pq
        if self._pinned is not None:
            pinned = np.zeros(self._pinned.shape[0], dtype=bool)
            pinned[:new_n] = self._pinned[:old_n][alive]
            self._pinned = pinned
        self._n = new_n
        self._deleted[:old_n] = False
        self._n_deleted = 0
        # every tombstone in the log is now reclaimed; consumers rebuild
        # their masks from the remap, not the log
        self._deleted_log.clear()
        self._deleted_log_base = 0
        for h in self._log_cursors:
            self._log_cursors[h] = 0
        # host/device caches of the old id space
        self._device_cache = None
        self._norms_cache = None
        self._device_norms = None
        self._alive_words = None
        self._q_norms_cache = None
        self._device_q = None
        self._device_q_scale = None
        self._device_q_norms = None
        self._device_pq = None
        self.compact_gen += 1
        return mapping

    def device_vectors(self) -> jnp.ndarray:
        if self._device_cache is None or self._device_cache.shape[0] != self._n:
            self._device_cache = jnp.asarray(self.vectors)
        return self._device_cache

    def sq_norms(self) -> np.ndarray:
        if self._norms_cache is None or self._norms_cache.shape[0] != self._n:
            self._norms_cache = np.einsum(
                "nd,nd->n", self.vectors, self.vectors).astype(np.float32)
        return self._norms_cache

    def device_sq_norms(self) -> jnp.ndarray:
        if (self._device_norms is None
                or self._device_norms.shape[0] != self._n):
            self._device_norms = jnp.asarray(self.sq_norms())
        return self._device_norms

    # ----------------------------------------------------- int8 scalar tier
    def _ensure_quantized(self) -> None:
        """Catch the int8 mirror up to the current row count: quantizes only
        the fresh ``[_q_n, _n)`` slice (post-normalization rows, so the
        codes always mirror exactly what the fp32 scan would score)."""
        if self._q_n == self._n and self._q_rows is not None:
            return
        cap = self._rows.shape[0]
        if self._q_rows is None or self._q_rows.shape[0] < cap:
            grown_q = np.zeros((cap, self.dim), dtype=np.int8)
            grown_s = np.ones(cap, dtype=np.float32)
            if self._q_rows is not None:
                grown_q[: self._q_n] = self._q_rows[: self._q_n]
                grown_s[: self._q_n] = self._q_scale[: self._q_n]
            self._q_rows, self._q_scale = grown_q, grown_s
        if self._q_n < self._n:
            codes, scales = quantize_rows(self._rows[self._q_n: self._n])
            self._q_rows[self._q_n: self._n] = codes
            self._q_scale[self._q_n: self._n] = scales
        self._q_n = self._n

    @property
    def q_vectors(self) -> np.ndarray:
        """(n, d) int8 codes (see :mod:`.quant` for the scoring contract)."""
        self._ensure_quantized()
        return self._q_rows[: self._n]

    @property
    def q_scales(self) -> np.ndarray:
        """(n,) fp32 per-row dequantization scales."""
        self._ensure_quantized()
        return self._q_scale[: self._n]

    def device_q_vectors(self) -> jnp.ndarray:
        if self._device_q is None or self._device_q.shape[0] != self._n:
            self._device_q = jnp.asarray(self.q_vectors)
        return self._device_q

    def device_q_scales(self) -> jnp.ndarray:
        if (self._device_q_scale is None
                or self._device_q_scale.shape[0] != self._n):
            self._device_q_scale = jnp.asarray(self.q_scales)
        return self._device_q_scale

    def q_sq_norms(self) -> np.ndarray:
        """(n,) fp32 squared norms of the *dequantized* rows — the ``||x||^2``
        term the int8 l2 scan subtracts, so int8 scores are exact for the
        quantized operands (scale^2 * sum(codes^2), int32-accumulated)."""
        if (self._q_norms_cache is None
                or self._q_norms_cache.shape[0] != self._n):
            codes = self.q_vectors.astype(np.int32)
            self._q_norms_cache = (
                np.einsum("nd,nd->n", codes, codes).astype(np.float32)
                * self.q_scales * self.q_scales)
        return self._q_norms_cache

    def device_q_sq_norms(self) -> jnp.ndarray:
        if (self._device_q_norms is None
                or self._device_q_norms.shape[0] != self._n):
            self._device_q_norms = jnp.asarray(self.q_sq_norms())
        return self._device_q_norms

    # ------------------------------------------------------------ PQ tier
    def _ensure_pq(self) -> None:
        """Catch the PQ mirror up to the current row count: trains the
        codebook once (on the rows present at first use), then encodes only
        the fresh ``[_pq_n, _n)`` slice with the frozen centroids."""
        if self._pq is None:
            self._pq = PQCodebook(self.dim, self._pq_m)
        cap = self._rows.shape[0]
        if self._pq_codes is None or self._pq_codes.shape[0] < cap:
            grown = np.zeros((cap, self._pq.m), dtype=np.uint8)
            if self._pq_codes is not None:
                grown[: self._pq_n] = self._pq_codes[: self._pq_n]
            self._pq_codes = grown
        if self._pq_n < self._n:
            if not self._pq.trained:
                self._pq.train(self._rows[: self._n])
            self._pq_codes[self._pq_n: self._n] = self._pq.encode(
                self._rows[self._pq_n: self._n])
            self._pq_n = self._n

    @property
    def pq_m(self) -> int:
        """PQ subspace count (known before the codebook is trained)."""
        if self._pq is not None:
            return self._pq.m
        return default_pq_m(self.dim) if self._pq_m is None else self._pq_m

    @property
    def pq_codebook(self) -> PQCodebook:
        self._ensure_pq()
        return self._pq

    @property
    def pq_codes(self) -> np.ndarray:
        """(n, M) uint8 PQ codes (see :class:`.quant.PQCodebook`)."""
        self._ensure_pq()
        return self._pq_codes[: self._n]

    def pq_lut(self, queries: np.ndarray) -> np.ndarray:
        """(nq, M, 256) fp32 per-query ADC tables for this store's metric."""
        return self.pq_codebook.lut(queries, self.metric)

    def device_pq_codes(self) -> jnp.ndarray:
        if self._device_pq is None or self._device_pq.shape[0] != self._n:
            self._device_pq = jnp.asarray(self.pq_codes)
        return self._device_pq

    # ------------------------------------------------------ tiered storage
    def set_device_budget(self, nbytes: Optional[int]) -> None:
        """Configure the device byte budget. Once the fp32 rows outgrow it,
        the store is *tiered*: fp32 rows live in host RAM, the device holds
        PQ codes (plus hot-pinned fp32 rows), and rescore windows fetch
        host rows on demand."""
        self._device_budget = None if nbytes is None else int(nbytes)

    @property
    def device_budget(self) -> Optional[int]:
        return self._device_budget

    def tiered_active(self) -> bool:
        return (self._device_budget is not None
                and self.nbytes() > self._device_budget)

    def pin_rows(self, ids) -> None:
        """Replace the set of device-pinned fp32 rows (scope-aware hot
        placement, chosen by the planner's access stats)."""
        mask = np.zeros(self._rows.shape[0], dtype=bool)
        ids = np.atleast_1d(np.asarray(ids, dtype=np.int64))
        ids = ids[(ids >= 0) & (ids < self._n)]
        mask[ids] = True
        self._pinned = mask

    def pinned_mask(self) -> Optional[np.ndarray]:
        """(n,) bool mask of device-pinned rows, or None when nothing is
        pinned. Ingest after a pin may grow the store past the mask built at
        pin time — new rows are unpinned until the next pin refresh, so the
        mask is padded with False up to the current row count."""
        if self._pinned is None:
            return None
        if self._pinned.shape[0] < self._n:
            grown = np.zeros(self._rows.shape[0], dtype=bool)
            grown[: self._pinned.shape[0]] = self._pinned
            self._pinned = grown
        return self._pinned[: self._n]

    def placement(self) -> Tuple[int, int]:
        """``(rows_device_pinned, rows_host)`` for alive rows. When the
        store is not tiered every row is device-resident (the fp32 device
        cache), so host count is 0."""
        alive = self.alive_count()
        if not self.tiered_active():
            return alive, 0
        pm = self.pinned_mask()
        if pm is None:
            return 0, alive
        pinned = int(np.count_nonzero(pm & ~self._deleted[: self._n]))
        return pinned, alive - pinned

    #: bounded-retry policy for transient host-fetch faults (a stalled or
    #: flaky host-RAM/disk read in the tiered store): up to FETCH_RETRIES
    #: re-attempts with exponential backoff starting at FETCH_BACKOFF_S.
    FETCH_RETRIES = 3
    FETCH_BACKOFF_S = 1e-3

    def fetch_rows(self, row_ids: np.ndarray) -> np.ndarray:
        """Gather exact fp32 rows by store id — the host-row fetch behind
        every ``gather_rescore`` window. In a tiered store this is the I/O
        edge (host RAM today, mmap/disk later), so it carries the
        ``store.host_fetch`` fault seam: transient faults are retried with
        exponential backoff up to :data:`FETCH_RETRIES` times (counted in
        ``host_fetch_retries``); exhaustion or a non-transient fault
        escalates to the caller, where the scheduler's degradation ladder
        takes over."""
        attempt = 0
        while True:
            try:
                faults.fire("store.host_fetch")
                return self.vectors[row_ids]
            except faults.TransientFault:
                if attempt >= self.FETCH_RETRIES:
                    self.host_fetch_failures += 1
                    raise faults.FaultError(
                        "store.host_fetch",
                        f"transient fault persisted past "
                        f"{self.FETCH_RETRIES} retries") from None
                time.sleep(self.FETCH_BACKOFF_S * (2 ** attempt))
                attempt += 1
                self.host_fetch_retries += 1

    # -------------------------------------------------------------- bytes
    def alive_count(self) -> int:
        return self._n - self._n_deleted

    def nbytes(self) -> int:
        return self._n * self.dim * 4

    def q_nbytes(self) -> int:
        """Device bytes of the int8 tier: codes + one fp32 scale per row."""
        return self._n * self.dim + self._n * 4

    def alive_nbytes(self) -> int:
        """fp32 bytes of rows that are actually alive — what accounting
        reports, so tombstoned rows can't flatter compression ratios."""
        return self.alive_count() * self.dim * 4

    def q_alive_nbytes(self) -> int:
        return self.alive_count() * (self.dim + 4)

    def pq_nbytes(self) -> int:
        """Device bytes of the PQ tier: uint8 codes of alive rows only.
        The O(1) codebook is reported separately
        (:meth:`pq_codebook_nbytes`), not amortized into per-row bytes."""
        self._ensure_pq()
        return self.alive_count() * self._pq.m

    def pq_codebook_nbytes(self) -> int:
        return self._pq.nbytes() if self._pq is not None else 0


class ShardedStoreView:
    """Row-sharded device mirror of a :class:`VectorStore` over a mesh.

    The device array is sized to a padded *capacity* (a multiple of
    ``32 * n_shards``, so every shard's local rows stay word-aligned for the
    packed scope masks) and shard ``s`` permanently owns rows
    ``[s*n_loc, (s+1)*n_loc)``. That fixed block layout is what makes ingest
    growth incremental: new rows land in-place via a device scatter touching
    only the shards that cover them, and only growth *past* the capacity
    forces a full re-shard — at a doubled capacity, so re-shard cost is
    amortized O(1) per ingested row (the same policy as ``IVFIndex.add``).
    Capacity-padding rows are zero vectors and are masked out by the packed
    alive mask (:meth:`alive_device`), which also carries the store-level
    tombstones."""

    def __init__(self, store: VectorStore, mesh):
        self.store = store
        self.mesh = mesh
        self.axes = tuple(mesh.axis_names)
        self.n_shards = int(np.prod([mesh.shape[a] for a in self.axes]))
        self.row_align = 32 * self.n_shards
        self._db = None
        self._cap = 0
        self._synced = 0
        self._alive = None               # device packed alive∧in-range words
        self._alive_host = None          # host mirror of the same words
        self._alive_n = 0                # rows covered by the mirror
        # registered tombstone-log cursor: consuming through the store API
        # (instead of indexing the raw list) is what lets the store drop
        # the consumed prefix instead of holding O(delete-history) forever
        self._log_consumer = store.register_log_consumer()
        self._compact_gen = store.compact_gen
        # int8 tier mirror (codes + per-row scales), built lazily on the
        # first quantized scan and then maintained through the same
        # incremental-scatter / capacity-re-shard policy as the fp32 rows
        self._qdb = None                 # (cap, dim) int8, row-sharded
        self._qscale = None              # (cap,) f32, row-sharded
        self._q_synced = 0
        # PQ tier mirror (uint8 codes), same lazy/incremental policy
        self._pqdb = None                # (cap, M) uint8, row-sharded
        self._pq_synced = 0
        self.db_bytes_uploaded = 0       # incremental row-scatter traffic
        self.alive_bytes_uploaded = 0    # alive-mask scatter traffic
        self.q_bytes_uploaded = 0        # int8 mirror scatter traffic
        self.pq_bytes_uploaded = 0       # PQ mirror scatter traffic
        self.reshards = 0                # full capacity re-shards

    @property
    def cap(self) -> int:
        return self._cap

    @property
    def n_loc(self) -> int:
        return self._cap // self.n_shards if self._cap else 0

    @property
    def n_words(self) -> int:
        return self._cap // 32

    @property
    def db(self):
        assert self._db is not None, "call sync() before reading the view"
        return self._db

    def _sharding(self, *spec):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def sync(self) -> bool:
        """Mirror any new store rows onto the mesh. Returns True when the
        padded capacity changed (a full re-shard: device-resident masks
        derived from the old capacity are invalid and must be rebuilt)."""
        n = len(self.store)
        # Seam: the mesh H2D staging edge — a transient fault here models a
        # stalled/failed device transfer; sync callers (staging, the sharded
        # launch) surface it to the scheduler's degradation ladder, which
        # downshifts the group to the flat executor.
        faults.fire("sharded.h2d")
        if self._compact_gen != self.store.compact_gen:
            # the store compacted underneath us without apply_remap (no
            # maintenance manager attached): every mirror row moved, so
            # force the full-rebuild path below
            self._compact_gen = self.store.compact_gen
            self._db = None
        if self._db is None or n > self._cap:
            cap = max(self._cap, self.row_align)
            while cap < n:
                cap *= 2
            host = np.zeros((cap, self.store.dim), dtype=np.float32)
            host[:n] = self.store.vectors
            self._db = jax.device_put(host, self._sharding(self.axes, None))
            self._cap = cap
            self._synced = n
            self.db_bytes_uploaded += host.nbytes
            self.reshards += 1
            self._alive = None
            self._qdb = None        # int8 mirror rebuilds at the new capacity
            self._pqdb = None       # PQ mirror likewise
            return True
        if n > self._synced:
            n_new = n - self._synced
            pad = _pow2_at_most(n_new, self._cap - self._synced)
            chunk = np.zeros((pad, self.store.dim), dtype=np.float32)
            chunk[:n_new] = self.store.vectors[self._synced:n]
            self._db = _scatter_rows(self._db, jnp.asarray(chunk),
                                     jnp.int32(self._synced))
            self.db_bytes_uploaded += n_new * self.store.dim * 4
            self._synced = n
        return False

    def q_device(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Row-sharded int8 mirror ``(codes (cap, d) int8, scales (cap,)
        f32)``. Built lazily on the first quantized scan (a gather-only or
        fp32-only workload never pays the upload) and maintained
        incrementally afterwards: fresh store rows land via the same
        power-of-two-padded device scatter as the fp32 mirror. Capacity
        padding rows are zero codes with zero scale — they score 0 and are
        masked by :meth:`alive_device` anyway. Call :meth:`sync` first."""
        assert self._db is not None, "call sync() before q_device()"
        n = len(self.store)
        if self._qdb is None:
            host_q = np.zeros((self._cap, self.store.dim), dtype=np.int8)
            host_q[:n] = self.store.q_vectors
            host_s = np.zeros(self._cap, dtype=np.float32)
            host_s[:n] = self.store.q_scales
            self._qdb = jax.device_put(host_q,
                                       self._sharding(self.axes, None))
            self._qscale = jax.device_put(host_s, self._sharding(self.axes))
            self.q_bytes_uploaded += host_q.nbytes + host_s.nbytes
            self._q_synced = n
        elif n > self._q_synced:
            n_new = n - self._q_synced
            pad = _pow2_at_most(n_new, self._cap - self._q_synced)
            chunk = np.zeros((pad, self.store.dim), dtype=np.int8)
            chunk[:n_new] = self.store.q_vectors[self._q_synced: n]
            self._qdb = _scatter_rows(self._qdb, jnp.asarray(chunk),
                                      jnp.int32(self._q_synced))
            sch = np.zeros(pad, dtype=np.float32)
            sch[:n_new] = self.store.q_scales[self._q_synced: n]
            self._qscale = _scatter_words(self._qscale, jnp.asarray(sch),
                                          jnp.int32(self._q_synced))
            self.q_bytes_uploaded += n_new * (self.store.dim + 4)
            self._q_synced = n
        return self._qdb, self._qscale

    def pq_device(self) -> jnp.ndarray:
        """Row-sharded PQ code mirror ``(cap, M) uint8``. Same lazy build /
        incremental power-of-two-padded scatter / re-shard-rebuild policy
        as :meth:`q_device`. Capacity-padding rows are code 0 — whatever
        they score, the packed alive mask zeroes them out. Call
        :meth:`sync` first."""
        assert self._db is not None, "call sync() before pq_device()"
        n = len(self.store)
        m = self.store.pq_codebook.m
        if self._pqdb is None:
            host = np.zeros((self._cap, m), dtype=np.uint8)
            host[:n] = self.store.pq_codes
            self._pqdb = jax.device_put(host,
                                        self._sharding(self.axes, None))
            self.pq_bytes_uploaded += host.nbytes
            self._pq_synced = n
        elif n > self._pq_synced:
            n_new = n - self._pq_synced
            pad = _pow2_at_most(n_new, self._cap - self._pq_synced)
            chunk = np.zeros((pad, m), dtype=np.uint8)
            chunk[:n_new] = self.store.pq_codes[self._pq_synced: n]
            self._pqdb = _scatter_rows(self._pqdb, jnp.asarray(chunk),
                                       jnp.int32(self._pq_synced))
            self.pq_bytes_uploaded += n_new * m
            self._pq_synced = n
        return self._pqdb

    def apply_remap(self) -> None:
        """Rebuild the row mirrors for a just-compacted store at the SAME
        padded capacity. Deliberately not a re-shard: the device mask
        table's word layout (``cap/32`` words per scope) survives, which is
        what lets :meth:`ShardedExecutor.apply_remap` *patch* its cached
        scope rows through the id remap instead of evicting every slot."""
        self._compact_gen = self.store.compact_gen
        if self._db is None:
            return
        n = len(self.store)
        host = np.zeros((self._cap, self.store.dim), dtype=np.float32)
        host[:n] = self.store.vectors
        self._db = jax.device_put(host, self._sharding(self.axes, None))
        self.db_bytes_uploaded += host.nbytes
        self._synced = n
        self._alive = None              # rebuilt from store state next read
        self._qdb = None
        self._pqdb = None
        self.store.log_consumer_reset(self._log_consumer)

    def _patch_alive_range(self, w_lo: int, w_hi: int) -> None:
        """Recompute words [w_lo, w_hi) from authoritative store state and
        scatter only that range to the device (power-of-two padded width)."""
        n_words = self._cap // 32
        w_hi = min(w_lo + _pow2_at_most(w_hi - w_lo, n_words - w_lo), n_words)
        n = len(self.store)
        g0, g1 = w_lo * 32, w_hi * 32
        seg = np.zeros(g1 - g0, dtype=bool)
        hi = min(n, g1)
        if hi > g0:
            seg[: hi - g0] = ~self.store.deleted_mask()[g0:hi]
        words = np.packbits(seg, bitorder="little").view(np.uint32)
        self._alive_host[w_lo:w_hi] = words
        self._alive = _scatter_words(self._alive, jnp.asarray(words),
                                     jnp.int32(w_lo))
        self.alive_bytes_uploaded += words.nbytes

    def alive_device(self):
        """(cap/32,) packed uint32 alive ∧ in-range mask on the mesh:
        capacity-padding rows and tombstoned rows are 0. Maintained
        incrementally — appended rows and newly tombstoned ids (from the
        store's tombstone log) patch only the word ranges they touch; a full
        rebuild happens only on a capacity re-shard."""
        n = len(self.store)
        if self._alive is None:
            padded = np.zeros(self._cap, dtype=bool)
            ab = self.store.alive_bool()
            padded[:n] = True if ab is None else ab
            host = np.packbits(padded, bitorder="little").view(np.uint32)
            self._alive_host = host
            self._alive = jax.device_put(host, self._sharding(self.axes))
            self.alive_bytes_uploaded += host.nbytes
            self._alive_n = n
            self.store.log_consumer_reset(self._log_consumer)
            return self._alive
        dirty: Optional[Tuple[int, int]] = None
        if n > self._alive_n:
            dirty = (self._alive_n >> 5, ((n - 1) >> 5) + 1)
            self._alive_n = n
        fresh = self.store.consume_deleted_log(self._log_consumer)
        if fresh:
            lo, hi = min(fresh) >> 5, (max(fresh) >> 5) + 1
            dirty = ((min(dirty[0], lo), max(dirty[1], hi))
                     if dirty else (lo, hi))
        if dirty is not None:
            self._patch_alive_range(*dirty)
        return self._alive
