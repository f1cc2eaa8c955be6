"""IVF (inverted-file) partition-based ANN executor (the paper's IVF path).

K-means (Lloyd) runs as a jit'd JAX loop. Partitions live in a device-resident
**padded-CSR layout**: one flat id array where every list occupies a
TILE-aligned region (padding slots hold the invalid id ``n``), plus per-list
offsets/lengths. Search is batched end to end — query→centroid distances and
``nprobe`` selection for the whole batch in one jit, then a single
score→top-k launch over the probed lists with the directory scope applied as
packed uint32 mask words: either the jnp twin ``_ivf_batch_jnp``, which
gathers the probed tiles, or the Pallas ``ivf_gather_topk`` kernel, which
streams each query's probed lists from a list-major device copy of the rows
(:class:`ListBlocks`) by block index.

The paper's finding that IVF shows a *flat* latency-vs-depth profile (Fig. 11)
falls out naturally: partition probing dominates and the scope intersection is
a cheap bitmap AND. ``search_loop`` keeps the original per-query host loop as
the reference oracle.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .costmodel import model_of
from .store import VectorStore

# Per-list padding granularity of the CSR layout. The jnp launch expands
# every probed list to the layout's widest padded region, so a small tile
# keeps that expansion tight.
TILE = 32
# Rows per block of the list-major store the Pallas IVF scan streams (a
# multiple of the 128-lane tiling; one 1 MB DMA per block at d=1024).
LIST_BLOCK = 256


@functools.partial(jax.jit, static_argnames=("n_iters",))
def _lloyd(data: jnp.ndarray, init: jnp.ndarray, n_iters: int) -> jnp.ndarray:
    """Plain Lloyd iterations; empty clusters keep their previous center."""

    def step(centers, _):
        d2 = (jnp.sum(data * data, axis=1)[:, None]
              - 2.0 * data @ centers.T
              + jnp.sum(centers * centers, axis=1)[None, :])
        assign = jnp.argmin(d2, axis=1)
        one_hot = jax.nn.one_hot(assign, centers.shape[0], dtype=data.dtype)
        counts = one_hot.sum(axis=0)
        sums = one_hot.T @ data
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1)[:, None],
                        centers)
        return new, None

    centers, _ = jax.lax.scan(step, init, None, length=n_iters)
    return centers


@jax.jit
def _assign(data: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    d2 = (jnp.sum(data * data, axis=1)[:, None]
          - 2.0 * data @ centers.T
          + jnp.sum(centers * centers, axis=1)[None, :])
    return jnp.argmin(d2, axis=1)


@dataclass(frozen=True)
class CSRLayout:
    """Device-resident padded-CSR partition layout. ``flat_ids`` is one flat
    int32 array; list ``c`` occupies ``[offsets[c], offsets[c]+aligned[c])``
    with its ``aligned[c] - len`` padding slots (and the final extra slot that
    out-of-region gathers clamp to) holding the invalid id ``n``.

    The fused launch expands every probed list to ``max_aligned`` (static
    shapes), so batch cost scales with the *widest* partition: heavily skewed
    k-means (one list holding most of the store) degrades the batched path
    toward a full scan. Keep ``n_lists`` sized so lists stay balanced."""
    offsets: jnp.ndarray     # (n_lists,) int32, TILE-aligned region starts
    aligned: jnp.ndarray     # (n_lists,) int32, padded region lengths
    flat_ids: jnp.ndarray    # (sum(aligned) + 1,) int32
    max_aligned: int         # static: widest padded region
    n: int                   # store size the sentinel was built for


@dataclass(frozen=True)
class ListBlocks:
    """Device-resident list-major copy of the store for the Pallas IVF
    scan: list ``c`` is blocks ``start[c] .. start[c] + nblk[c] - 1`` of
    ``LIST_BLOCK`` rows each (at least one block; the tail holds id -1 and
    zero rows). The kernel streams a query's probed lists from here by
    block index, so the copy costs one store's worth of device memory and
    no query ever materializes its candidate rows."""
    rows: jnp.ndarray        # (n_blocks * LIST_BLOCK, d)
    ids: jnp.ndarray         # (n_blocks, LIST_BLOCK) int32, -1 = padding
    sq: jnp.ndarray          # (n_blocks, LIST_BLOCK) f32 (zeros unless l2)
    start: jnp.ndarray       # (n_lists,) int32
    nblk: jnp.ndarray        # (n_lists,) int32
    max_blocks: int          # static: the longest list's block count
    layout: "CSRLayout"      # the CSR layout this copy was built from


def _probe(queries, centers, nprobe: int):
    """(B, nprobe) nearest lists per query, nearest first. Centroid
    distances use the elementwise (q-c)^2 form so every element depends only
    on its own (query, center) pair — batch-size invariant, which keeps
    dsq_batch bit-identical to the per-request loop."""
    d2 = jnp.sum((queries[:, None, :] - centers[None, :, :]) ** 2, axis=-1)
    return jax.lax.top_k(-d2, nprobe)[1]


_probe_jit = jax.jit(_probe, static_argnames=("nprobe",))


def _probe_and_expand(queries, centers, offsets, aligned, flat_ids,
                      nprobe: int, max_aligned: int):
    """Whole-batch probe selection + candidate-tile expansion: (B, C) store
    ids of the probed lists' CSR regions, padding = the invalid id n."""
    probe = _probe(queries, centers, nprobe)              # (B, nprobe)
    off = jnp.take(offsets, probe)                        # (B, nprobe)
    algn = jnp.take(aligned, probe)
    within = jnp.arange(max_aligned, dtype=jnp.int32)
    idx = off[..., None] + within[None, None, :]
    idx = jnp.where(within[None, None, :] < algn[..., None],
                    idx, flat_ids.shape[0] - 1)           # clamp to sentinel
    return jnp.take(flat_ids, idx).reshape(queries.shape[0], -1)   # (B, C)


@functools.partial(
    jax.jit, static_argnames=("k", "nprobe", "max_aligned", "metric"))
def _ivf_batch_jnp(queries, centers, offsets, aligned, flat_ids, data, sq,
                   words, sids, k: int, nprobe: int, max_aligned: int,
                   metric: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-launch batched IVF: probe -> gather -> scope-mask -> top-k.
    The jnp twin of the Pallas ``ivf_gather_topk`` kernel.

    The probe stage is batch-size invariant (elementwise distances), so the
    candidate set per query is always identical to the per-request loop's;
    candidate scoring uses the fast batched dot_general, whose low score
    bits may differ across batch shapes (same top-k members barring exact
    score ties — the same caveat as the flat path's fused kernel)."""
    n = data.shape[0]
    cand = _probe_and_expand(queries, centers, offsets, aligned, flat_ids,
                             nprobe, max_aligned)         # (B, C), n=invalid
    valid = cand < n
    safe = jnp.where(valid, cand, 0)
    rows = jnp.take(data, safe, axis=0)                   # (B, C, d)
    scores = jax.lax.dot_general(
        rows, queries, (((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)               # (B, C)
    if metric == "l2":
        scores = 2.0 * scores - jnp.take(sq, safe)
    qwords = jnp.take(words, sids, axis=0)                # (B, n_words)
    qbits = jnp.take_along_axis(qwords, safe >> 5, axis=1)
    bit = (qbits >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
    mask = valid & (bit != 0)
    scores = jnp.where(mask, scores, -jnp.inf)
    vals, loc = jax.lax.top_k(scores, k)
    ids = jnp.take_along_axis(cand, loc, axis=1)
    ids = jnp.where(jnp.isfinite(vals), ids, -1)
    return vals, ids


@functools.partial(
    jax.jit, static_argnames=("k", "nprobe", "max_aligned", "metric"))
def _ivf_batch_i8(queries, q_i8, q_scale, centers, offsets, aligned,
                  flat_ids, q_rows, row_scale, q_sq, words, sids, k: int,
                  nprobe: int, max_aligned: int, metric: str
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int8 phase of the two-phase batched IVF launch: probe (always fp32 —
    the probed partition set must stay identical to the fp32 path's so the
    two precisions explore the same candidates), gather the *int8 codes*
    of the probed tiles, score int8 with merge-time scales, scope-mask,
    top-``k`` (= rescore_k) candidate ids for the caller's exact fp32
    gather-rescore."""
    n = q_rows.shape[0]
    cand = _probe_and_expand(queries, centers, offsets, aligned, flat_ids,
                             nprobe, max_aligned)         # (B, C), n=invalid
    valid = cand < n
    safe = jnp.where(valid, cand, 0)
    from .quant import int_exact_dot
    rows8 = jnp.take(q_rows, safe, axis=0)                # (B, C, d) int8
    s = int_exact_dot(rows8, q_i8, (((2,), (1,)), ((0,), (0,))))  # (B, C)
    scores = s * (jnp.take(row_scale, safe) * q_scale[:, None])
    if metric == "l2":
        scores = 2.0 * scores - jnp.take(q_sq, safe)
    qwords = jnp.take(words, sids, axis=0)                # (B, n_words)
    qbits = jnp.take_along_axis(qwords, safe >> 5, axis=1)
    bit = (qbits >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
    mask = valid & (bit != 0)
    scores = jnp.where(mask, scores, -jnp.inf)
    vals, loc = jax.lax.top_k(scores, k)
    ids = jnp.take_along_axis(cand, loc, axis=1)
    ids = jnp.where(jnp.isfinite(vals), ids, -1)
    return vals, ids


@functools.partial(
    jax.jit, static_argnames=("k", "nprobe", "max_aligned"))
def _ivf_batch_pq(queries, lut, centers, offsets, aligned, flat_ids,
                  pq_codes, words, sids, k: int, nprobe: int,
                  max_aligned: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """PQ/ADC phase of the two-phase batched IVF launch: probe (always fp32,
    same partition sets as every other precision), gather the *uint8 PQ
    codes* of the probed tiles (M bytes per candidate instead of 4*dim),
    sum each candidate's LUT entries, scope-mask, top-``k`` (= rescore_k)
    candidate ids for the caller's exact fp32 gather-rescore. Metric-free:
    the per-query LUT folds it in."""
    n = pq_codes.shape[0]
    cand = _probe_and_expand(queries, centers, offsets, aligned, flat_ids,
                             nprobe, max_aligned)         # (B, C), n=invalid
    valid = cand < n
    safe = jnp.where(valid, cand, 0)
    codes = jnp.take(pq_codes, safe, axis=0)              # (B, C, M) uint8
    sel = jnp.take_along_axis(
        lut, codes.transpose(0, 2, 1).astype(jnp.int32), axis=2)  # (B, M, C)
    scores = jnp.sum(sel, axis=1)                         # (B, C)
    qwords = jnp.take(words, sids, axis=0)                # (B, n_words)
    qbits = jnp.take_along_axis(qwords, safe >> 5, axis=1)
    bit = (qbits >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
    mask = valid & (bit != 0)
    scores = jnp.where(mask, scores, -jnp.inf)
    vals, loc = jax.lax.top_k(scores, k)
    ids = jnp.take_along_axis(cand, loc, axis=1)
    ids = jnp.where(jnp.isfinite(vals), ids, -1)
    return vals, ids


@jax.jit
def _list_major(vectors, sq, ids):
    """List-major copy of the store rows (zero rows on padding) and their
    squared norms, both addressed by the (n_blocks, block) id table."""
    safe = jnp.maximum(ids, 0)
    live = ids >= 0
    rows = jnp.where(live.reshape(-1, 1),
                     jnp.take(vectors, safe.reshape(-1), axis=0), 0)
    return rows, jnp.where(live, jnp.take(sq, safe), 0.0)


class IVFIndex:
    name = "ivf"

    def __init__(self, store: VectorStore, n_lists: int = 64,
                 n_iters: int = 10, seed: int = 0):
        self.store = store
        self.n_lists = n_lists
        data = store.vectors
        rng = np.random.default_rng(seed)
        init = data[rng.choice(len(data), size=min(n_lists, len(data)),
                               replace=False)]
        if len(init) < n_lists:  # degenerate tiny stores
            init = np.concatenate(
                [init, rng.normal(size=(n_lists - len(init), store.dim))
                 .astype(np.float32)])
        self.centers = np.asarray(_lloyd(jnp.asarray(data), jnp.asarray(init),
                                         n_iters))
        assign = np.asarray(_assign(jnp.asarray(data), jnp.asarray(self.centers)))
        # amortized-capacity member arrays: _data[c][:_len[c]] are list c's ids
        order = np.argsort(assign, kind="stable")
        counts = np.bincount(assign, minlength=n_lists)
        starts = np.concatenate([[0], np.cumsum(counts)])
        sorted_ids = order.astype(np.uint32)
        self._data: List[np.ndarray] = []
        self._len = np.zeros(n_lists, dtype=np.int64)
        for c in range(n_lists):
            members = sorted_ids[starts[c]: starts[c + 1]]
            arr = np.empty(max(8, len(members)), dtype=np.uint32)
            arr[: len(members)] = members
            self._data.append(arr)
            self._len[c] = len(members)
        self.assign = assign
        self._layout: Optional[CSRLayout] = None
        self._blocks: Optional[ListBlocks] = None
        self._centers_dev: Optional[jnp.ndarray] = None
        # bumped by every completed repartition(); the maintenance journal's
        # idempotence probe on crash replay
        self.repartition_gen = 0

    @property
    def lists(self) -> List[np.ndarray]:
        """Trimmed per-partition id views (capacity tails excluded)."""
        return [d[: int(ln)] for d, ln in zip(self._data, self._len)]

    def _append(self, c: int, new: np.ndarray) -> None:
        ln = int(self._len[c])
        need = ln + len(new)
        cur = self._data[c]
        if need > len(cur):           # amortized doubling, not per-call concat
            grown = np.empty(max(2 * len(cur), need), dtype=np.uint32)
            grown[:ln] = cur[:ln]
            self._data[c] = cur = grown
        cur[ln:need] = new
        self._len[c] = need

    def add(self, ids: np.ndarray) -> None:
        """Route freshly-added store rows into their partitions."""
        ids = np.asarray(ids, dtype=np.uint32)
        if len(ids) == 0:
            return
        rows = self.store.vectors[ids]
        assign = np.asarray(_assign(jnp.asarray(rows), jnp.asarray(self.centers)))
        for c in np.unique(assign):
            self._append(int(c), ids[assign == c])
        self._layout = None
        self._blocks = None

    def layout(self) -> CSRLayout:
        """Build (or reuse) the device-resident padded-CSR layout."""
        if self._layout is None or self._layout.n != len(self.store):
            aligned = ((self._len + TILE - 1) // TILE) * TILE
            offsets = np.zeros(self.n_lists, dtype=np.int64)
            if self.n_lists > 1:
                np.cumsum(aligned[:-1], out=offsets[1:])
            n = len(self.store)
            flat = np.full(int(aligned.sum()) + 1, n, dtype=np.int32)
            for c in range(self.n_lists):
                ln = int(self._len[c])
                flat[offsets[c]: offsets[c] + ln] = self._data[c][:ln]
            self._layout = CSRLayout(
                offsets=jnp.asarray(offsets.astype(np.int32)),
                aligned=jnp.asarray(aligned.astype(np.int32)),
                flat_ids=jnp.asarray(flat),
                max_aligned=int(aligned.max()) if self.n_lists else 0,
                n=n)
        return self._layout

    def list_blocks(self) -> ListBlocks:
        """Build (or reuse) the list-major device copy of the rows, in step
        with :meth:`layout` (rebuilt whenever the layout is)."""
        lay = self.layout()
        if self._blocks is None or self._blocks.layout is not lay:
            self._blocks = None            # free the old copy first
            nblk = np.maximum(1, -(-self._len // LIST_BLOCK))
            start = np.zeros(self.n_lists, dtype=np.int64)
            np.cumsum(nblk[:-1], out=start[1:])
            ids = np.full((int(nblk.sum()), LIST_BLOCK), -1, dtype=np.int32)
            flat = ids.reshape(-1)
            for c in range(self.n_lists):
                ln = int(self._len[c])
                off = int(start[c]) * LIST_BLOCK
                flat[off: off + ln] = self._data[c][:ln]
            sq = (self.store.device_sq_norms() if self.store.metric == "l2"
                  else jnp.zeros(max(len(self.store), 1), jnp.float32))
            ids_d = jnp.asarray(ids)
            rows, sq_lm = _list_major(self.store.device_vectors(), sq, ids_d)
            self._blocks = ListBlocks(
                rows=rows, ids=ids_d, sq=sq_lm,
                start=jnp.asarray(start.astype(np.int32)),
                nblk=jnp.asarray(nblk.astype(np.int32)),
                max_blocks=int(nblk.max()), layout=lay)
        return self._blocks

    def probe(self, queries: np.ndarray, nprobe: int) -> np.ndarray:
        """(B, nprobe) lists each query probes, nearest first — the device
        probe of every batched search."""
        if self._centers_dev is None:
            self._centers_dev = jnp.asarray(self.centers)
        return np.asarray(_probe_jit(
            jnp.asarray(np.atleast_2d(queries), jnp.float32),
            self._centers_dev, nprobe=int(nprobe)))

    def nbytes(self) -> int:
        return self.centers.nbytes + sum(d.nbytes for d in self._data)

    # ------------------------------------------------------------ maintenance
    def pad_waste(self) -> int:
        """Padding slots the current partition occupancy forces into the CSR
        layout (sum of TILE-aligned region lengths minus live list lengths).
        Grows under churn: tombstoned members keep their slots and drifted
        ingest piles into a few hot lists, whose ragged tails all round up."""
        aligned = ((self._len + TILE - 1) // TILE) * TILE
        return int(aligned.sum() - self._len.sum())

    def partition_stats(self) -> dict:
        """Occupancy summary for the maintenance planner's drift detector."""
        lens = self._len
        aligned = ((lens + TILE - 1) // TILE) * TILE
        return {
            "n_lists": self.n_lists,
            "pad_waste": int(aligned.sum() - lens.sum()),
            "max_len": int(lens.max()) if self.n_lists else 0,
            "mean_len": float(lens.mean()) if self.n_lists else 0.0,
            "max_aligned": int(aligned.max()) if self.n_lists else 0,
        }

    def _current_assign(self, n: int) -> np.ndarray:
        """Per-row partition of record, derived from the member lists (the
        ``assign`` array goes stale after :meth:`add`)."""
        cur = np.full(n, -1, dtype=np.int64)
        for c in range(self.n_lists):
            cur[self._data[c][: int(self._len[c])].astype(np.int64)] = c
        return cur

    def repartition(self, seed: int = 0, n_iters: int = 10,
                    sample: Optional[int] = None) -> dict:
        """Retrain centroids on a seeded sample of the *alive* rows, re-assign
        every row, and rebuild the member lists aside before one atomic
        attribute swap (readers see either the old partitioning or the new,
        never a mix). Tombstoned rows are dropped from the rebuilt lists, so
        repartitioning also reclaims their CSR slots. Deterministic for a
        fixed (store contents, seed, n_iters, sample) — crash replay re-runs
        it bit-identically."""
        n = len(self.store)
        waste_before = self.pad_waste()
        if n == 0:
            self.repartition_gen += 1
            return {"gen": self.repartition_gen, "moved": 0,
                    "pad_waste_before": waste_before, "pad_waste_after": 0}
        data = self.store.vectors
        alive = self.store.alive_bool()
        pool = np.nonzero(alive)[0] if alive is not None else np.arange(n)
        rng = np.random.default_rng(seed)
        if sample is not None and 0 < sample < len(pool):
            pool = np.sort(pool[rng.choice(len(pool), size=sample,
                                           replace=False)])
        centers = self.centers
        if len(pool):
            centers = np.asarray(_lloyd(jnp.asarray(data[pool]),
                                        jnp.asarray(self.centers), n_iters))
        assign = np.asarray(_assign(jnp.asarray(data), jnp.asarray(centers)))
        old_assign = self._current_assign(n)
        # rebuild member lists aside: alive rows only, ascending id per list
        keep = np.ones(n, dtype=bool) if alive is None else alive
        order = np.argsort(assign, kind="stable")
        order = order[keep[order]]
        counts = np.bincount(assign[keep], minlength=self.n_lists)
        starts = np.concatenate([[0], np.cumsum(counts)])
        sorted_ids = order.astype(np.uint32)
        new_data: List[np.ndarray] = []
        new_len = np.zeros(self.n_lists, dtype=np.int64)
        for c in range(self.n_lists):
            members = sorted_ids[starts[c]: starts[c + 1]]
            arr = np.empty(max(8, len(members)), dtype=np.uint32)
            arr[: len(members)] = members
            new_data.append(arr)
            new_len[c] = len(members)
        moved = int(np.sum((old_assign >= 0) & keep & (old_assign != assign)))
        self.centers = centers
        self._data = new_data
        self._len = new_len
        self.assign = assign
        self._layout = None
        self._blocks = None
        self._centers_dev = None
        self.repartition_gen += 1
        return {"gen": self.repartition_gen, "moved": moved,
                "pad_waste_before": waste_before,
                "pad_waste_after": self.pad_waste()}

    def remap_ids(self, mapping) -> None:
        """Rewrite member ids through a store-compaction ``mapping`` (old row
        -> new row, -1 = reclaimed). Centers are untouched — compaction moves
        encodings, not vectors — and dropped rows leave their lists, so the
        rebuilt CSR sheds their padding."""
        m = np.asarray(mapping, dtype=np.int64)
        for c in range(self.n_lists):
            ln = int(self._len[c])
            members = m[self._data[c][:ln].astype(np.int64)]
            members = members[members >= 0].astype(np.uint32)
            arr = np.empty(max(8, len(members)), dtype=np.uint32)
            arr[: len(members)] = members
            self._data[c] = arr
            self._len[c] = len(members)
        new_n = int(np.sum(m >= 0))
        self.assign = self._current_assign(new_n)
        self._layout = None
        self._blocks = None

    # ----------------------------------------------------------------- search
    def search(self, queries: np.ndarray, k: int,
               candidate_ids: Optional[np.ndarray] = None,
               nprobe: Optional[int] = None, precision: str = "fp32",
               rescore_k: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Probe nprobe partitions per query; returns (scores, ids) (q, k).
        Device-batched single-scope front door over :meth:`search_multi`.
        ``nprobe=None`` asks the store's cost model (hand-set 8 under the
        heuristic model; the measured recall-floored depth when
        calibrated)."""
        queries = np.atleast_2d(np.asarray(queries, dtype=np.float32))
        n = len(self.store)
        from .store import pack_ids_to_words
        words = pack_ids_to_words(candidate_ids, n)
        sids = np.zeros(queries.shape[0], dtype=np.int32)
        return self.search_multi(queries, words[None, :], sids, k,
                                 nprobe=nprobe, precision=precision,
                                 rescore_k=rescore_k)

    def search_multi(self, queries: np.ndarray, mask_words: np.ndarray,
                     scope_ids: np.ndarray, k: int,
                     nprobe: Optional[int] = None,
                     use_pallas: bool = False, precision: str = "fp32",
                     rescore_k: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """One launch for a heterogeneous scope batch: queries (B, d), packed
        scope masks (n_scopes, ceil(n/32)) uint32, per-query scope row ids
        (B,). Tombstoned rows are ANDed out of every scope before the launch.
        Returns (scores, ids) both (B, k); ids int64 with -1 padding.

        ``precision="int8"`` gathers the probed tiles' *int8 codes* instead
        of fp32 rows (a quarter of the gather bytes), keeps the scope-masked
        top-``rescore_k`` per query, and finishes with the shared exact fp32
        gather-rescore — the probe stage stays fp32 either way, so both
        precisions explore identical partition sets."""
        from .quant import quantize_rows, resolve_rescore_k
        queries = np.atleast_2d(np.asarray(queries, dtype=np.float32))
        B = queries.shape[0]
        out_scores = np.full((B, k), -np.inf, dtype=np.float32)
        out_ids = np.full((B, k), -1, dtype=np.int64)
        n = len(self.store)
        if n == 0:
            return out_scores, out_ids
        lay = self.layout()
        if nprobe is None:
            nprobe = model_of(self.store).default_nprobe(self.n_lists)
        nprobe = int(max(1, min(nprobe, self.n_lists)))
        C = nprobe * lay.max_aligned
        if C == 0:
            return out_scores, out_ids
        mask_words = np.asarray(mask_words, dtype=np.uint32)
        alive = self.store.alive_words()
        if alive is not None:
            mask_words = mask_words & alive[None, :]
        if self._centers_dev is None:
            self._centers_dev = jnp.asarray(self.centers)
        words_d = jnp.asarray(mask_words)
        sids_d = jnp.asarray(scope_ids, dtype=jnp.int32)
        if precision == "int8":
            from .flat import gather_rescore
            r = min(resolve_rescore_k(k, rescore_k, n), C)
            q_i8, q_s = quantize_rows(queries)
            q_sq = (self.store.device_q_sq_norms()
                    if self.store.metric == "l2"
                    else jnp.zeros(0, dtype=jnp.float32))
            _, cand = _ivf_batch_i8(
                jnp.asarray(queries), jnp.asarray(q_i8), jnp.asarray(q_s),
                self._centers_dev, lay.offsets, lay.aligned, lay.flat_ids,
                self.store.device_q_vectors(), self.store.device_q_scales(),
                q_sq, words_d, sids_d, k=r, nprobe=nprobe,
                max_aligned=lay.max_aligned, metric=self.store.metric)
            return gather_rescore(self.store, queries,
                                  np.asarray(cand, dtype=np.int64), k)
        if precision == "pq":
            from .flat import gather_rescore
            r = min(resolve_rescore_k(k, rescore_k, n), C)
            lut = jnp.asarray(self.store.pq_lut(queries))
            _, cand = _ivf_batch_pq(
                jnp.asarray(queries), lut, self._centers_dev,
                lay.offsets, lay.aligned, lay.flat_ids,
                self.store.device_pq_codes(), words_d, sids_d, k=r,
                nprobe=nprobe, max_aligned=lay.max_aligned)
            return gather_rescore(self.store, queries,
                                  np.asarray(cand, dtype=np.int64), k)
        kk = min(k, C)
        if use_pallas:
            from ..kernels import ops as kops
            from .flat import bucket, pad_rows
            blocks = self.list_blocks()
            bp = bucket(B)
            qp = jnp.asarray(pad_rows(queries, bp))
            probe = _probe_jit(qp, self._centers_dev, nprobe=nprobe)
            qwords = jnp.take(words_d, jnp.asarray(
                pad_rows(np.asarray(scope_ids, np.int32), bp)), axis=0)
            vals, ids = kops.ivf_gather_topk(
                qp, probe, blocks.start, blocks.nblk, blocks.rows,
                blocks.ids, blocks.sq, qwords, max_blocks=blocks.max_blocks,
                k=kk, metric=self.store.metric)
            vals, ids = vals[:B], ids[:B]
        else:
            # sq is only read on the (trace-time static) l2 branch; skip the
            # O(n) host→device transfer entirely for ip/cos
            sq = (self.store.device_sq_norms() if self.store.metric == "l2"
                  else jnp.zeros(0, dtype=jnp.float32))
            vals, ids = _ivf_batch_jnp(
                jnp.asarray(queries), self._centers_dev, lay.offsets,
                lay.aligned, lay.flat_ids, self.store.device_vectors(), sq,
                words_d, sids_d, k=kk, nprobe=nprobe,
                max_aligned=lay.max_aligned, metric=self.store.metric)
        vals = np.array(vals, dtype=np.float32)
        ids = np.asarray(ids, dtype=np.int64)
        vals[ids < 0] = -np.inf
        out_scores[:, :kk] = vals
        out_ids[:, :kk] = ids
        return out_scores, out_ids

    def search_loop(self, queries: np.ndarray, k: int,
                    candidate_ids: Optional[np.ndarray] = None,
                    nprobe: Optional[int] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Per-query host loop — the pre-batching reference oracle the
        device path is tested against."""
        queries = np.atleast_2d(np.asarray(queries, dtype=np.float32))
        nq = queries.shape[0]
        # same elementwise (q-c)^2 form as the device probe stage, so both
        # paths rank near-equidistant centroids identically
        qc = np.sum((queries[:, None, :] - self.centers[None, :, :]) ** 2,
                    axis=-1)
        if nprobe is None:
            nprobe = model_of(self.store).default_nprobe(self.n_lists)
        nprobe = int(max(1, min(nprobe, self.n_lists)))
        # stable sort breaks exact-distance ties by lowest index, same as the
        # device path's lax.top_k
        probe = np.argsort(qc, axis=1, kind="stable")[:, :nprobe]
        cand_mask: Optional[np.ndarray] = None
        if candidate_ids is not None:
            cand_mask = np.zeros(len(self.store), dtype=bool)
            cand_mask[candidate_ids] = True
        alive = self.store.alive_bool()
        if alive is not None:
            cand_mask = alive if cand_mask is None else cand_mask & alive
        out_scores = np.full((nq, k), -np.inf, dtype=np.float32)
        out_ids = np.full((nq, k), -1, dtype=np.int64)
        metric = self.store.metric
        data = self.store.vectors
        lists = self.lists
        for qi in range(nq):
            cands = np.concatenate([lists[c] for c in probe[qi]])
            if cand_mask is not None and len(cands):
                cands = cands[cand_mask[cands]]
            if len(cands) == 0:
                continue
            rows = data[cands]
            if metric in ("ip", "cos"):
                scores = rows @ queries[qi]
            else:
                scores = 2.0 * rows @ queries[qi] - np.sum(rows * rows, axis=1)
            kk = min(k, len(cands))
            sel = np.argpartition(scores, -kk)[-kk:]
            order = sel[np.argsort(scores[sel])[::-1]]
            out_scores[qi, :kk] = scores[order]
            out_ids[qi, :kk] = cands[order]
        return out_scores, out_ids
