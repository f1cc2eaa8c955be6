"""Sharded multi-device serving executor — the mesh as a first-class
``dsq_batch`` executor.

``dsq_batch(..., executor="sharded")`` plans exactly like the flat path
(gather below the selectivity threshold, scan above; same epoch-validated
``ScopeMaskCache``), but every scan-plan group in the batch ranks on a
row-sharded device mesh in ONE ``shard_map`` launch
(:func:`distributed.search.make_sharded_batch_search`):

* the store rows live device-resident via :class:`ShardedStoreView`
  (incremental row scatter on ingest, amortized-doubling re-shard on growth
  past capacity);
* each unique scope's packed uint32 mask words occupy a *slot* of a
  device-resident scope table sharded on the word dim — each shard holds
  exactly the words covering its rows — validated by the same scope-epoch
  tokens as the host cache, so a repeated scope never re-uploads;
* TrieHI ``DSMDelta`` events patch surviving slots **in place** with a
  word-range scatter (only the words spanning the moved aggregate travel to
  the device) instead of forcing a re-resolve + full row re-upload;
* store-level tombstones ride the packed alive mask, ANDed in-register.

Gather-plan groups (selective scopes, |C| << N) stay on the single-device
gather launch — a full mesh sweep for a 50-row scope would waste every
shard — by delegating to the shared :class:`FlatExecutor` machinery, which
also keeps the batch bit-identical to the flat path by construction. The
scan side scores with the flat twin's full-precision fp32 product and
keeps top-k tie order through the shard-order merge (ties resolve to the
lowest global id on both paths); its scores may differ from the flat
path's by a few fp32 ULP, because XLA rounds a matmul differently per
operand shape and a shard sees fewer rows.
"""
from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .costmodel import model_of
from .flat import (FlatExecutor, bucket, choose_plan, gather_rescore,
                   pad_rows, pad_topk)
from .quant import quantize_rows, resolve_rescore_k
from .store import ShardedStoreView, VectorStore, pack_ids_to_words


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_table_row(table: jnp.ndarray, row: jnp.ndarray,
                       slot) -> jnp.ndarray:
    """In-place (donated) row scatter into the device scope table — only the
    row's words travel, never an O(slots * words) table copy. Donation is
    safe here because every caller is the serving thread (the same thread
    that launches against the table); the DSM delta thread must use the
    copying functional update instead — donating a buffer the serving
    thread may be launching against would invalidate it mid-flight."""
    return jax.lax.dynamic_update_slice(table, row[None, :], (slot, 0))


class _Slot:
    """One scope table row: device-resident packed words + validity evidence
    (the same scope-epoch token contract as ``planner.CachedScope``)."""
    __slots__ = ("slot", "tokens", "n")

    def __init__(self, slot: int, tokens, n: int):
        self.slot = slot
        self.tokens = tokens     # None == never valid (uncacheable scope)
        self.n = n


class ShardedExecutor:
    name = "sharded"

    def __init__(self, store: VectorStore, mesh=None, table_slots: int = 64):
        if mesh is None:
            from ..launch.mesh import make_mesh_for_devices
            mesh = make_mesh_for_devices()
        self.store = store
        self.mesh = mesh
        self.view = ShardedStoreView(store, mesh)
        self.flat = FlatExecutor(store)      # gather-plan twin
        self.table_slots = table_slots
        self._slots: "OrderedDict[Tuple[str, object], _Slot]" = OrderedDict()
        self._free: List[int] = []
        self._host_table: Optional[np.ndarray] = None   # (S, W) mirror
        self._table = None                               # device (S, W)
        self._fns: Dict[Tuple[int, int], object] = {}    # (cap, k) -> jit fn
        self._fns_i8: Dict[Tuple[int, int], object] = {}  # (cap, r) -> jit fn
        self._fns_pq: Dict[Tuple[int, int], object] = {}  # (cap, r) -> jit fn
        self._lock = threading.Lock()        # serving vs DSM delta threads
        # lifetime accounting (the per-batch deltas land in BatchAccounting)
        self.mask_bytes_uploaded = 0
        self.mask_bytes_patched = 0
        self.masks_patched = 0
        self.masks_evicted = 0
        self.launches = 0

    @property
    def n_shards(self) -> int:
        return self.view.n_shards

    # --------------------------------------------------------------- syncing
    def sync(self) -> None:
        """Mirror store growth onto the mesh; a capacity re-shard changes the
        word length, so the whole scope table rebuilds (every slot's words
        were packed for the old capacity). The reset happens under the lock:
        a DSM delta thread may be iterating the slots concurrently."""
        changed = self.view.sync()
        with self._lock:
            if changed or self._table is None:
                self._reset_table()
                # compiled launches for superseded capacities are unreachable
                # (the key always uses the current cap) — drop them
                cap = self.view.cap
                self._fns = {key: fn for key, fn in self._fns.items()
                             if key[0] == cap}
                self._fns_i8 = {key: fn for key, fn in self._fns_i8.items()
                                if key[0] == cap}
                self._fns_pq = {key: fn for key, fn in self._fns_pq.items()
                                if key[0] == cap}

    def reserve(self, n_scopes: int) -> None:
        """Grow the scope table so one batch's scan groups all fit. Without
        this, pinning scope ``table_slots + 1`` of a batch would LRU-evict a
        slot pinned earlier in the *same* batch — whose recorded slot id
        would then rank against the wrong mask."""
        if n_scopes <= self.table_slots:
            return
        with self._lock:
            while self.table_slots < n_scopes:
                self.table_slots *= 2
            self._reset_table()

    def _reset_table(self) -> None:
        W = max(self.view.n_words, 1)
        self._host_table = np.zeros((self.table_slots, W), dtype=np.uint32)
        self._table = jax.device_put(
            self._host_table, self.view._sharding(None, self.view.axes))
        self._slots.clear()
        self._free = list(range(self.table_slots))

    def _fn(self, k: int):
        key = (self.view.cap, k)
        fn = self._fns.get(key)
        if fn is None:
            from ..distributed.search import make_sharded_batch_search
            fn = make_sharded_batch_search(self.mesh, self.view.cap,
                                           self.store.dim, k,
                                           self.store.metric)
            self._fns[key] = fn
        return fn

    def _fn_i8(self, r: int):
        key = (self.view.cap, r)
        fn = self._fns_i8.get(key)
        if fn is None:
            from ..distributed.search import make_sharded_batch_search_i8
            fn = make_sharded_batch_search_i8(self.mesh, self.view.cap,
                                              self.store.dim, r,
                                              self.store.metric)
            self._fns_i8[key] = fn
        return fn

    def _fn_pq(self, r: int):
        key = (self.view.cap, r)
        fn = self._fns_pq.get(key)
        if fn is None:
            from ..distributed.search import make_sharded_batch_search_pq
            fn = make_sharded_batch_search_pq(self.mesh, self.view.cap,
                                              self.store.pq_codebook.m, r)
            self._fns_pq[key] = fn
        return fn

    # ----------------------------------------------------------- scope table
    def ensure_scope(self, namespace: str, key, entry) -> Tuple[int, bool]:
        """Pin a planned scope into the device table; returns
        ``(slot, hit)``. Token-validated: a slot whose stored tokens still
        equal the entry's is served without any upload — ``hit=True`` —
        including after a DSM delta patched both sides to the same advanced
        epoch."""
        with self._lock:
            assert self._table is not None, "sync() before ensure_scope()"
            tk = (namespace, key)
            si = self._slots.get(tk)
            tokens = entry.tokens if entry.tokens else None
            if (si is not None and si.tokens is not None
                    and si.tokens == tokens and si.n == entry.n):
                self._slots.move_to_end(tk)
                return si.slot, True
            if si is None:
                if not self._free:
                    _, old = self._slots.popitem(last=False)   # LRU evict
                    self._free.append(old.slot)
                    self.masks_evicted += 1
                slot = self._free.pop()
            else:
                slot = si.slot                                 # refresh
            row = np.zeros(self.view.n_words, dtype=np.uint32)
            w = entry.words
            row[: len(w)] = w
            self._host_table[slot] = row
            self._table = _scatter_table_row(self._table, jnp.asarray(row),
                                             jnp.int32(slot))
            self.mask_bytes_uploaded += row.nbytes
            self._slots[tk] = _Slot(slot, tokens, entry.n)
            self._slots.move_to_end(tk)
            return slot, False

    # --------------------------------------------------------- delta patching
    def apply_delta(self, event, namespace: str = "fs") -> None:
        """``DSMDelta`` listener (one subscription per namespace): patch the
        shard-resident words of every surviving slot with a word-range
        scatter — only the ``[w_lo, w_hi)`` words spanning the moved
        aggregate travel to the device — and advance the slot token to the
        patched epoch. Slots whose stored epoch does not equal the event's
        pre-op epoch, or whose scope composes non-trivially (exclusions,
        non-recursive anchors), evict instead; same rules as
        ``ScopeMaskCache.apply_delta``."""
        removed = {id(n): (o, e) for n, o, e in event.removed_from}
        added = {id(n): (o, e) for n, o, e in event.added_to}
        if not removed and not added:
            return
        with self._lock:
            if self._table is None or not self._slots:
                return
            arr = event.delta.to_array()
            if len(arr):
                w_lo = int(arr[0]) >> 5
                w_hi = (int(arr[-1]) >> 5) + 1
                dw = event.delta.to_words(w_hi * 32)[w_lo:w_hi]
            else:
                w_lo = w_hi = 0
                dw = None
            evict = []
            for tk, si in self._slots.items():
                ns, key = tk
                if ns != namespace or si.tokens is None:
                    continue
                hit = [t for t in si.tokens
                       if (id(t[0]) in removed or id(t[0]) in added)]
                if not hit:
                    continue                   # off-chain slot: untouched
                if (len(si.tokens) == 1 and not key.exclude and key.recursive
                        and w_hi <= self._host_table.shape[1]):
                    # (a delta reaching past the table's word capacity means
                    # the store outgrew the view since the last sync — the
                    # next sync re-shards and rebuilds the table anyway, so
                    # such slots evict rather than half-patch)
                    node, cur_epoch = si.tokens[0]
                    sign = 1 if id(node) in added else -1
                    old_e, new_e = (added[id(node)] if sign > 0
                                    else removed[id(node)])
                    if cur_epoch == old_e:
                        if dw is not None:
                            cur = self._host_table[si.slot, w_lo:w_hi]
                            patched = (cur | dw) if sign > 0 else (cur & ~dw)
                            self._host_table[si.slot, w_lo:w_hi] = patched
                            # copying functional update, NOT the donated
                            # scatter: this runs on the DSM thread while the
                            # serving thread may be mid-launch on the table
                            self._table = self._table.at[
                                si.slot, w_lo:w_hi].set(jnp.asarray(patched))
                            self.mask_bytes_patched += patched.nbytes
                        si.tokens = ((node, new_e),)
                        self.masks_patched += 1
                        continue
                evict.append(tk)
            for tk in evict:
                si = self._slots.pop(tk)
                self._free.append(si.slot)
                self.masks_evicted += 1

    def apply_remap(self, mapping) -> int:
        """Store-compaction id remap: re-mirror the compacted rows at the
        *unchanged* shard capacity (``ShardedStoreView.apply_remap`` — no
        re-shard, so the table's word layout survives) and rewrite every
        pinned slot's packed words through ``mapping`` instead of evicting.
        Tokens carry over — compaction moves id encodings, not directory
        membership, and the paired ``ScopeMaskCache.apply_remap`` advances
        the host cache the same way, so slot hits keep validating. Returns
        the number of slots patched."""
        self.view.apply_remap()
        m = np.asarray(mapping, dtype=np.int64)
        old_n = len(m)
        alive_old = np.nonzero(m >= 0)[0]
        new_n = len(alive_old)
        with self._lock:
            if self._table is None or not self._slots:
                return 0
            W = self._host_table.shape[1]
            patched = 0
            for _, si in self._slots.items():
                row = self._host_table[si.slot]
                bits = np.unpackbits(row.view(np.uint8),
                                     bitorder="little")[:old_n]
                new_bits = np.zeros(W * 32, dtype=np.uint8)
                new_bits[m[alive_old]] = bits[alive_old]
                new_row = np.packbits(new_bits,
                                      bitorder="little").view(np.uint32)
                self._host_table[si.slot] = new_row
                # copying functional update, NOT the donated scatter: the
                # maintenance thread patches while serving may be mid-launch
                self._table = self._table.at[si.slot].set(jnp.asarray(new_row))
                si.n = new_n
                self.mask_bytes_patched += new_row.nbytes
                patched += 1
            self.masks_patched += patched
            return patched

    # --------------------------------------------------------------- queries
    def phase_depth(self, k: int, precision: str = "fp32",
                    rescore_k: Optional[int] = None) -> int:
        """Per-shard top-k depth the scan launch must support: ``k`` for the
        exact fp32 scan, the effective ``rescore_k`` for the int8 phase."""
        if precision in ("int8", "pq"):
            return resolve_rescore_k(k, rescore_k, len(self.store))
        return k

    def scan_on_mesh(self, k: int, precision: str = "fp32",
                     rescore_k: Optional[int] = None) -> bool:
        """The per-shard local top-k needs that many local rows; tiny stores
        (or huge k / rescore_k) fall back to the single-device flat twin,
        bit-identically (fp32) / same-two-phase (int8)."""
        depth = self.phase_depth(k, precision, rescore_k)
        return 0 < depth <= self.view.n_loc

    def search_slots(self, queries: np.ndarray, slot_ids: np.ndarray,
                     k: int, precision: str = "fp32",
                     rescore_k: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """ONE shard_map launch ranking every scan-plan request of the batch
        against the device-resident scope table. Same result contract as
        ``FlatExecutor.search_multi``: (B, k) scores/ids, ids == -1 where the
        scope ran out of candidates. ``precision="int8"``: the mesh scans
        the sharded int8 mirror, each shard keeps rescore_k local
        candidates, the shard-merge replicates the global rescore_k set, and
        ONE exact fp32 gather-rescore on the host store ranks the final k."""
        queries = np.atleast_2d(np.asarray(queries, dtype=np.float32))
        # pad the batch to a bucket size: a few compiled launches serve
        # every batch shape (padding rows read slot 0 and are dropped)
        b = len(queries)
        qp = pad_rows(queries, bucket(b))
        sids = pad_rows(np.asarray(slot_ids, np.int32), bucket(b))
        if precision in ("int8", "pq"):
            r = self.phase_depth(k, precision, rescore_k)
            launch = self._launch_i8 if precision == "int8" else \
                self._launch_pq
            cand = launch(qp, self._table, sids, r)[:b]
            return gather_rescore(self.store, queries, cand, k)
        scores, ids = self._launch(qp, self._table, sids, k)
        scores, ids = scores[:b], ids[:b]
        ids[~np.isfinite(scores)] = -1
        return scores, ids

    def _launch(self, queries, table, sids, k):
        fn = self._fn(k)
        s, i = fn(self.view.db, table, self.view.alive_device(),
                  jnp.asarray(np.asarray(sids, dtype=np.int32)),
                  jnp.asarray(queries))
        self.launches += 1
        return np.asarray(s), np.asarray(i, dtype=np.int64)

    def _launch_i8(self, queries, table, sids, r) -> np.ndarray:
        """int8 scan phase on the mesh: returns the merged (B, r) global
        candidate ids (-1 where a scope ran dry)."""
        qdb, qscale = self.view.q_device()
        q_i8, q_s = quantize_rows(queries)
        fn = self._fn_i8(r)
        s, i = fn(qdb, qscale, table, self.view.alive_device(),
                  jnp.asarray(np.asarray(sids, dtype=np.int32)),
                  jnp.asarray(q_i8), jnp.asarray(q_s))
        self.launches += 1
        cand = np.asarray(i, dtype=np.int64)
        cand[~np.isfinite(np.asarray(s))] = -1
        return cand

    def _launch_pq(self, queries, table, sids, r) -> np.ndarray:
        """PQ/ADC scan phase on the mesh: the per-query LUTs build on the
        host (one (B, M, 256) einsum against the frozen codebook), each
        shard sums its slice of the sharded uint8 code mirror, and the
        shard-merge replicates the global (B, r) candidate ids (-1 where a
        scope ran dry). The caller's single gather-rescore is the only
        host-fetch of fp32 rows on this path — the tiered-storage window."""
        lut = self.store.pq_lut(queries)
        fn = self._fn_pq(r)
        s, i = fn(self.view.pq_device(), table, self.view.alive_device(),
                  jnp.asarray(np.asarray(sids, dtype=np.int32)),
                  jnp.asarray(lut))
        self.launches += 1
        cand = np.asarray(i, dtype=np.int64)
        cand[~np.isfinite(np.asarray(s))] = -1
        return cand

    def search(self, queries: np.ndarray, k: int,
               candidate_ids: Optional[np.ndarray] = None,
               plan: Optional[str] = None, precision: str = "fp32",
               rescore_k: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Single-scope front door, mirroring ``FlatExecutor.search``'s plan
        decision; the scan plan runs on the mesh (an ad-hoc one-row scope
        table, no slot pinned). Results match the flat executor (same ids,
        scores within a few fp32 ULP) for any candidate set free of
        tombstoned ids — which every
        DSQ path guarantees, since scope resolution drops deleted entries.
        A stale caller-supplied id set containing tombstones diverges on the
        scan plan only: the mesh ANDs the store tombstone mask in-register,
        so deleted rows cannot resurface there (the flat twin would score
        them). ``precision="int8"`` follows the same plan decision with the
        two-phase pipeline: gather delegates to the flat twin's int8 gather,
        scan runs the sharded int8 mirror + one global fp32 rescore."""
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
        kk = min(k, m)
        if plan == "gather":
            return self.flat.search(queries, k, candidate_ids=candidate_ids,
                                    plan=plan, precision=precision,
                                    rescore_k=rescore_k)
        self.sync()
        if not self.scan_on_mesh(kk, precision, rescore_k):
            return self.flat.search(queries, k, candidate_ids=candidate_ids,
                                    plan=plan, precision=precision,
                                    rescore_k=rescore_k)
        words = np.zeros(self.view.n_words, dtype=np.uint32)
        w = pack_ids_to_words(candidate_ids, n)
        words[: len(w)] = w
        if precision == "int8":
            r = self.phase_depth(kk, precision, rescore_k)
            cand = self._launch_i8(queries, jnp.asarray(words[None, :]),
                                   np.zeros(queries.shape[0], np.int32), r)
            return gather_rescore(self.store, queries, cand, k)
        if precision == "pq":
            r = self.phase_depth(kk, precision, rescore_k)
            cand = self._launch_pq(queries, jnp.asarray(words[None, :]),
                                   np.zeros(queries.shape[0], np.int32), r)
            return gather_rescore(self.store, queries, cand, k)
        scores, ids = self._launch(queries, jnp.asarray(words[None, :]),
                                   np.zeros(queries.shape[0], np.int32), kk)
        # a lane can only exhaust when the candidate set held tombstoned ids
        # (scan implies m > k live candidates otherwise): honor the -1
        # sentinel contract rather than surfacing an arbitrary row
        ids[~np.isfinite(scores)] = -1
        return pad_topk(scores, ids, k)

    # ------------------------------------------------------------ inspection
    def stats(self) -> Dict[str, int]:
        return {"n_shards": self.n_shards, "cap": self.view.cap,
                "reshards": self.view.reshards,
                "db_bytes_uploaded": self.view.db_bytes_uploaded,
                "q_bytes_uploaded": self.view.q_bytes_uploaded,
                "slots": len(self._slots),
                "mask_bytes_uploaded": self.mask_bytes_uploaded,
                "mask_bytes_patched": self.mask_bytes_patched,
                "masks_patched": self.masks_patched,
                "masks_evicted": self.masks_evicted,
                "launches": self.launches}
