"""Distributed directory-scoped vector search over the production mesh.

The vector store is sharded row-wise across *all* mesh devices (a 512-chip pod
pair holds ~billions of 1024-d bf16 rows). A DSQ executes as:

  host: TrieHI resolves the directory scope -> per-shard packed bitmask
  device (shard_map, all axes manual):
      local masked top-k (the Pallas scoped_topk shape, here jnp for SPMD)
   -> all_gather of (k, score, global-id) triples   [O(devices*k) bytes]
   -> final top-k merge, replicated result

This mirrors the paper's architecture (scope resolution feeds the ANN
executor) at pod scale; the collective term is tiny by design, making the scan
compute/memory-bound — see the "viking-scan" rows produced by
``python -m repro.launch.dryrun --viking-scan`` (results/dryrun/) and the
``benchmarks.bench_roofline`` section of ``benchmarks.run``.

:func:`make_sharded_batch_search` is the serving-tier entry point consumed by
``vectordb.sharded.ShardedExecutor``: the same row-sharded scan, but ranking a
whole heterogeneous request batch against a device-resident packed scope-mask
table in ONE launch (scope-id indirection, tombstones ANDed in-register).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def _merge_local_topk(v, i, axes, n_dev: int, n_loc: int, k: int):
    """Shard-order merge of per-shard top-k triples: all_gather the
    (score, global-id) pairs, then one final top_k. Concatenation is
    shard-major with each shard's block already index-ordered, so exact
    score ties resolve to the lowest global id — bit-compatible with a
    single-device full-array top_k. Shared by every search builder below;
    a drift between copies would silently break that contract."""
    shard = jax.lax.axis_index(axes)
    gi = i.astype(jnp.int32) + shard * n_loc
    av = jax.lax.all_gather(v, axes, tiled=False)            # (n_dev, q, k)
    ai = jax.lax.all_gather(gi, axes, tiled=False)
    av = av.transpose(1, 0, 2).reshape(-1, n_dev * k)
    ai = ai.transpose(1, 0, 2).reshape(-1, n_dev * k)
    fv, fi = jax.lax.top_k(av, k)
    return fv, jnp.take_along_axis(ai, fi, axis=1)


def make_scoped_search(mesh: Mesh, n_total: int, dim: int, k: int,
                       metric: str = "ip", dtype=None):
    """Builds search(db, mask, queries) jitted for ``mesh``.

    db    : (n_total, dim)  sharded over all mesh axes on dim 0
    mask  : (n_total,) int8 scope mask, sharded identically
    queries: (q, dim) replicated
    Returns (scores (q,k), global ids (q,k)) replicated.
    """
    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    assert n_total % n_dev == 0, (n_total, n_dev)
    n_loc = n_total // n_dev

    def local_search(db_l, mask_l, q):
        # int8-quantized stores upcast in-register: HBM bytes halve vs bf16
        if db_l.dtype == jnp.int8:
            db_l = db_l.astype(jnp.bfloat16) * jnp.bfloat16(1.0 / 127)
        scores = jnp.einsum("qd,nd->qn", q.astype(db_l.dtype), db_l,
                            preferred_element_type=jnp.float32)
        if metric == "l2":
            scores = 2 * scores - jnp.sum(
                db_l.astype(jnp.float32) ** 2, axis=-1)[None, :]
        scores = jnp.where(mask_l[None, :] != 0, scores, -jnp.inf)
        v, i = jax.lax.top_k(scores, k)                      # (q, k) local
        return _merge_local_topk(v, i, axes, n_dev, n_loc, k)

    fn = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return jax.jit(fn)


def make_multi_scope_search(mesh: Mesh, n_total: int, dim: int, k: int,
                            metric: str = "ip"):
    """Batched heterogeneous-scope variant of :func:`make_scoped_search`.

    The host hands the device mesh ONE packed scope-mask matrix per request
    batch instead of one dense int8 mask per request:

    db        : (n_total, dim)         sharded row-wise over all mesh axes
    mask_words: (n_scopes, n_total/32) packed uint32, sharded on the word dim
                (each shard holds exactly the words covering its rows —
                32x less mask traffic than the dense int8 hand-off)
    scope_ids : (q,) int32             replicated; row into mask_words
    queries   : (q, dim)               replicated

    One shard_map launch ranks the whole mixed-scope batch; the collective
    stays the same O(devices*k) triple gather."""
    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    assert n_total % n_dev == 0, (n_total, n_dev)
    n_loc = n_total // n_dev
    assert n_loc % 32 == 0, (n_loc, "local rows must be word-aligned")

    def local_search(db_l, words_l, sids, q):
        if db_l.dtype == jnp.int8:
            db_l = db_l.astype(jnp.bfloat16) * jnp.bfloat16(1.0 / 127)
        scores = jnp.einsum("qd,nd->qn", q.astype(db_l.dtype), db_l,
                            preferred_element_type=jnp.float32)
        if metric == "l2":
            scores = 2 * scores - jnp.sum(
                db_l.astype(jnp.float32) ** 2, axis=-1)[None, :]
        # unpack this shard's packed words in-register: (n_scopes, n_loc)
        from ..kernels.ref import unpack_words_ref
        masks = unpack_words_ref(words_l, n_loc)
        valid = jnp.take(masks, sids, axis=0)                # (q, n_loc)
        scores = jnp.where(valid, scores, -jnp.inf)
        v, i = jax.lax.top_k(scores, k)
        return _merge_local_topk(v, i, axes, n_dev, n_loc, k)

    fn = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(P(axes, None), P(None, axes), P(None), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return jax.jit(fn)


def make_sharded_batch_search(mesh: Mesh, n_total: int, dim: int, k: int,
                              metric: str = "ip"):
    """Serving-tier launch: batched heterogeneous-scope scan over a
    device-resident scope table, tombstone-aware.

    db     : (n_total, dim) float32    sharded row-wise over all mesh axes
    words  : (n_scopes, n_total/32)    packed uint32 scope-mask table,
                                       sharded on the word dim (each shard
                                       holds the words covering its rows)
    alive  : (n_total/32,) uint32      packed alive/in-range mask, sharded
                                       like one table row (tombstoned rows
                                       and capacity-padding rows are 0)
    sids   : (q,) int32                replicated; row into ``words``
    queries: (q, dim) float32          replicated

    Differences from :func:`make_multi_scope_search`: the mask matrix is a
    persistent *table* (slots owned by ``ShardedExecutor``, patched in place
    by DSM deltas) rather than a per-batch stack, the tombstone mask is ANDed
    in-register, and the ip/cos scoring is the flat scan's full-precision
    fp32 product. The merged (scores, ids) match the flat batch path up to
    the rounding of the shard-local matmul shape: the same ids (k-boundary
    ties aside) with scores a few fp32 ULP apart — XLA rounds a dot
    differently per operand shape, so bit-identity cannot hold across
    backends. (The l2 norm term is computed in-kernel here, while the flat
    twin reads the store's cached device norms.)"""
    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    assert n_total % n_dev == 0, (n_total, n_dev)
    n_loc = n_total // n_dev
    assert n_loc % 32 == 0, (n_loc, "local rows must be word-aligned")
    assert 0 < k <= n_loc, (k, n_loc, "per-shard top-k must fit local rows")

    def local_search(db_l, words_l, alive_l, sids, q):
        # the flat scan's exact fp32 scoring (``flat.exact_scores``)
        scores = jnp.matmul(q, db_l.T, precision=jax.lax.Precision.HIGHEST)
        if metric == "l2":
            scores = 2.0 * scores - jnp.sum(db_l * db_l, axis=-1)[None, :]
        from ..kernels.ref import unpack_words_ref
        qwords = jnp.take(words_l, sids, axis=0) & alive_l[None, :]
        valid = unpack_words_ref(qwords, n_loc)              # (q, n_loc)
        scores = jnp.where(valid, scores, -jnp.inf)
        v, i = jax.lax.top_k(scores, k)
        return _merge_local_topk(v, i, axes, n_dev, n_loc, k)

    fn = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(P(axes, None), P(None, axes), P(axes), P(None),
                  P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return jax.jit(fn)


def make_sharded_batch_search_i8(mesh: Mesh, n_total: int, dim: int, r: int,
                                 metric: str = "ip"):
    """int8 scan phase of the two-phase sharded plan.

    Each shard scores its slice of the *int8 scalar-quantized* store —
    reading a quarter of the fp32 HBM bytes — keeps its local top-``r``
    (``r`` = rescore_k), and the shard-order merge replicates a global
    top-``r`` candidate set. The caller (``ShardedExecutor``) then runs ONE
    exact fp32 gather-rescore over the merged candidates, so the mesh never
    touches fp32 rows on the scan path at all.

    qdb    : (n_total, dim) int8      codes, sharded row-wise over all axes
    qscale : (n_total,) float32       per-row dequantization scales, sharded
    words  : (n_scopes, n_total/32)   packed scope table, sharded on words
    alive  : (n_total/32,) uint32     packed alive/in-range mask, sharded
    sids   : (q,) int32               replicated; row into ``words``
    q_i8   : (q, dim) int8            quantized queries, replicated
    q_scale: (q,) float32             query scales, replicated

    Returns (int8-phase scores (q, r), global ids (q, r)) replicated; the
    scores are the quantized approximations (callers rescore, not rank, by
    them). The int8 dot rides the f32 GEMM while exact (every partial sum an
    integer < 2^24 — ``flat._int_exact_dot``'s trade), so backends without a
    fast int8 MXU path still scan correctly."""
    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    assert n_total % n_dev == 0, (n_total, n_dev)
    n_loc = n_total // n_dev
    assert n_loc % 32 == 0, (n_loc, "local rows must be word-aligned")
    assert 0 < r <= n_loc, (r, n_loc, "per-shard top-r must fit local rows")

    def local_search(qdb_l, qscale_l, words_l, alive_l, sids, q_i8, q_scale):
        from ..vectordb.quant import int_exact_dot
        s = int_exact_dot(q_i8, qdb_l)
        scores = s * (q_scale[:, None] * qscale_l[None, :])
        if metric == "l2":
            codes = qdb_l.astype(jnp.float32)
            sq = jnp.sum(codes * codes, axis=-1) * qscale_l * qscale_l
            scores = 2.0 * scores - sq[None, :]
        from ..kernels.ref import unpack_words_ref
        qwords = jnp.take(words_l, sids, axis=0) & alive_l[None, :]
        valid = unpack_words_ref(qwords, n_loc)              # (q, n_loc)
        scores = jnp.where(valid, scores, -jnp.inf)
        v, i = jax.lax.top_k(scores, r)
        return _merge_local_topk(v, i, axes, n_dev, n_loc, r)

    fn = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(None, axes), P(axes), P(None),
                  P(None, None), P(None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return jax.jit(fn)


def make_sharded_batch_search_pq(mesh: Mesh, n_total: int, m: int, r: int):
    """PQ/ADC scan phase of the two-phase sharded plan.

    Each shard scores its slice of the *uint8 PQ codes* — ``m`` bytes per
    row instead of ``4*dim`` — by summing per-query LUT entries, keeps its
    local top-``r`` (``r`` = rescore_k), and the shard-order merge
    replicates a global top-``r`` candidate set. The caller then runs ONE
    exact fp32 gather-rescore over the merged candidates from the host
    store, so the mesh never touches fp32 rows on the scan path at all.

    pqdb  : (n_total, m) uint8        PQ codes, sharded row-wise over axes
    words : (n_scopes, n_total/32)    packed scope table, sharded on words
    alive : (n_total/32,) uint32      packed alive/in-range mask, sharded
    sids  : (q,) int32                replicated; row into ``words``
    lut   : (q, m, 256) float32       per-query ADC tables, replicated —
                                      the metric is folded in by
                                      ``PQCodebook.lut`` so this builder
                                      takes no metric argument

    Returns (ADC-phase scores (q, r), global ids (q, r)) replicated; the
    scores are approximations (callers rescore, not rank, by them). Scoring
    uses the same per-subspace take-accumulate loop as the single-device
    twin (``flat._adc_scores``): no (q, n_loc, m) fp32 intermediate."""
    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    assert n_total % n_dev == 0, (n_total, n_dev)
    n_loc = n_total // n_dev
    assert n_loc % 32 == 0, (n_loc, "local rows must be word-aligned")
    assert 0 < r <= n_loc, (r, n_loc, "per-shard top-r must fit local rows")

    def local_search(pqdb_l, words_l, alive_l, sids, lut):
        c = pqdb_l.astype(jnp.int32)                         # (n_loc, m)
        scores = jnp.take(lut[:, 0, :], c[:, 0], axis=1)     # (q, n_loc)
        for mm in range(1, m):
            scores = scores + jnp.take(lut[:, mm, :], c[:, mm], axis=1)
        from ..kernels.ref import unpack_words_ref
        qwords = jnp.take(words_l, sids, axis=0) & alive_l[None, :]
        valid = unpack_words_ref(qwords, n_loc)              # (q, n_loc)
        scores = jnp.where(valid, scores, -jnp.inf)
        v, i = jax.lax.top_k(scores, r)
        return _merge_local_topk(v, i, axes, n_dev, n_loc, r)

    fn = jax.shard_map(
        local_search, mesh=mesh,
        in_specs=(P(axes, None), P(None, axes), P(axes), P(None),
                  P(None, None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return jax.jit(fn)


def search_input_specs(mesh: Mesh, n_total: int, dim: int, n_queries: int,
                       dtype=jnp.bfloat16):
    """ShapeDtypeStructs + shardings for the dry-run of the scan step."""
    axes = tuple(mesh.axis_names)
    db = jax.ShapeDtypeStruct((n_total, dim), dtype)
    mask = jax.ShapeDtypeStruct((n_total,), jnp.int8)
    q = jax.ShapeDtypeStruct((n_queries, dim), jnp.bfloat16)
    shardings = (NamedSharding(mesh, P(axes, None)),
                 NamedSharding(mesh, P(axes)),
                 NamedSharding(mesh, P(None, None)))
    return (db, mask, q), shardings


def multi_scope_search_input_specs(mesh: Mesh, n_total: int, dim: int,
                                   n_queries: int, n_scopes: int,
                                   dtype=jnp.float32):
    """Multi-scope (packed words + scope ids) variant of
    :func:`search_input_specs`: ShapeDtypeStructs + shardings matching the
    :func:`make_sharded_batch_search` signature, so ``launch/dryrun.py`` can
    lower/compile the batched sharded scan without materializing a store."""
    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    assert n_total % (32 * n_dev) == 0, (n_total, n_dev)
    n_words = n_total // 32
    db = jax.ShapeDtypeStruct((n_total, dim), dtype)
    words = jax.ShapeDtypeStruct((n_scopes, n_words), jnp.uint32)
    alive = jax.ShapeDtypeStruct((n_words,), jnp.uint32)
    sids = jax.ShapeDtypeStruct((n_queries,), jnp.int32)
    q = jax.ShapeDtypeStruct((n_queries, dim), jnp.float32)
    shardings = (NamedSharding(mesh, P(axes, None)),
                 NamedSharding(mesh, P(None, axes)),
                 NamedSharding(mesh, P(axes)),
                 NamedSharding(mesh, P(None)),
                 NamedSharding(mesh, P(None, None)))
    return (db, words, alive, sids, q), shardings
