"""PQ/ADC device tier + scope-aware tiered fp32 storage.

Contracts under test:

* codebook mechanics — subspace k-means training, frozen-codebook
  incremental encoding, the metric-folding LUT identity (ADC score ==
  decoded-approximation score for ip/l2/cos);
* PQ Pallas kernels == numpy oracles across block shapes, empty scopes and
  all-masked tiles;
* two-phase executor contract — the PQ phase only *selects* candidates, the
  exact fp32 gather-rescore ranks, so exhaustive ``rescore_k`` reproduces
  the fp32 top-k set on flat/sharded;
* planner precision selection, alive-row byte accounting (tombstones
  excluded), tiered-storage placement/fetch accounting, and the fp32→pq
  auto-upgrade when the store exceeds its device byte budget.
"""
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels.ref import multi_scope_topk_pq_ref, scoped_topk_pq_ref
from repro.vectordb import DirectoryVectorDB
from repro.vectordb.flat import FlatExecutor
from repro.vectordb.planner import BatchAccounting
from repro.vectordb.quant import PQCodebook, default_pq_m
from repro.vectordb.sharded import ShardedExecutor
from repro.vectordb.store import VectorStore, pack_ids_to_words

RNG = np.random.default_rng(0)
DIM = 32


# ---------------------------------------------------------------- codebook
def test_default_pq_m():
    assert default_pq_m(16) == 4
    assert default_pq_m(24) == 6
    assert default_pq_m(32) == 8
    assert default_pq_m(64) == 16
    assert 64 % default_pq_m(64) == 0


def test_codebook_requires_divisible_m():
    with pytest.raises(ValueError):
        PQCodebook(dim=32, m=5)


def test_codebook_roundtrip_and_compression():
    rows = RNG.normal(size=(800, DIM)).astype(np.float32)
    cb = PQCodebook(DIM)
    cb.train(rows)
    codes = cb.encode(rows)
    assert codes.dtype == np.uint8 and codes.shape == (800, cb.m)
    back = cb.decode(codes)
    # decoded approximation is closer to the row than a random other row
    err = np.linalg.norm(back - rows, axis=1).mean()
    base = np.linalg.norm(rows[RNG.permutation(800)] - rows, axis=1).mean()
    assert err < 0.5 * base
    assert codes.nbytes == 800 * cb.m == rows.nbytes // (4 * DIM // cb.m)


@pytest.mark.parametrize("metric", ["ip", "l2", "cos"])
def test_lut_adc_identity(metric):
    """sum_m lut[m, code_m] must equal the executor's scoring expression
    evaluated on the decoded approximation (the ADC correctness identity;
    for l2 that is the larger-is-better ``2 q.x - ||x||^2`` form)."""
    rows = RNG.normal(size=(300, DIM)).astype(np.float32)
    q = RNG.normal(size=(5, DIM)).astype(np.float32)
    cb = PQCodebook(DIM)
    cb.train(rows)
    codes = cb.encode(rows)
    back = cb.decode(codes)
    lut = cb.lut(q, metric)
    assert lut.shape == (5, cb.m, 256)
    adc = lut[:, np.arange(cb.m)[None, :], codes.astype(np.int64)].sum(axis=2)
    if metric == "l2":
        want = 2.0 * q @ back.T - np.einsum("nd,nd->n", back, back)[None, :]
    else:
        want = q @ back.T
    np.testing.assert_allclose(adc, want, rtol=1e-4, atol=1e-4)


def test_store_incremental_pq_maintenance():
    """Codes always mirror encode(all rows) under the frozen codebook,
    through incremental adds and capacity growth; the codebook trains once
    and never re-trains (stable codes for already-encoded rows)."""
    st = VectorStore(DIM, "ip", capacity=4)
    st.add(RNG.normal(size=(70, DIM)).astype(np.float32))
    first = st.pq_codes.copy()
    cb = st.pq_codebook
    st.add(RNG.normal(size=(50, DIM)).astype(np.float32))
    assert st.pq_codebook is cb                       # frozen, not retrained
    np.testing.assert_array_equal(st.pq_codes[:70], first)
    np.testing.assert_array_equal(st.pq_codes, cb.encode(st.vectors))
    assert st.pq_nbytes() == len(st) * cb.m
    assert st.pq_nbytes() <= 0.08 * st.alive_nbytes()


def test_store_alive_byte_accounting_excludes_tombstones():
    st = VectorStore(DIM, "ip")
    st.add(RNG.normal(size=(100, DIM)).astype(np.float32))
    assert st.alive_nbytes() == st.nbytes()
    assert st.q_alive_nbytes() == st.q_nbytes()
    m = st.pq_codebook.m
    st.mark_deleted(np.arange(10))
    assert st.alive_nbytes() == 90 * DIM * 4
    assert st.q_alive_nbytes() == 90 * (DIM + 4)
    assert st.pq_nbytes() == 90 * m
    assert st.nbytes() == 100 * DIM * 4      # buffer bytes: unchanged


def test_sharded_view_pq_mirror_incremental():
    st = VectorStore(DIM, "ip")
    st.add(RNG.normal(size=(40, DIM)).astype(np.float32))
    ex = ShardedExecutor(st)
    ex.sync()
    pq = ex.view.pq_device()
    assert pq.dtype == np.uint8 and pq.shape == (ex.view.cap, st.pq_codebook.m)
    np.testing.assert_array_equal(np.asarray(pq)[:40], st.pq_codes)
    up0 = ex.view.pq_bytes_uploaded
    if ex.view.cap - len(st) > 2:
        st.add(RNG.normal(size=(2, DIM)).astype(np.float32))
        ex.sync()
        pq = ex.view.pq_device()
        np.testing.assert_array_equal(np.asarray(pq)[:42], st.pq_codes)
        assert 0 < ex.view.pq_bytes_uploaded - up0 < up0
    st.add(RNG.normal(size=(ex.view.cap, DIM)).astype(np.float32))
    ex.sync()
    pq = ex.view.pq_device()
    assert pq.shape[0] == ex.view.cap
    np.testing.assert_array_equal(np.asarray(pq)[: len(st)], st.pq_codes)


# ----------------------------------------------------------------- kernels
def _pq_fixture(nq, n, m, seed):
    rng = np.random.default_rng(seed)
    lut = rng.normal(size=(nq, m, 256)).astype(np.float32)
    codes = rng.integers(0, 256, size=(n, m)).astype(np.uint8)
    return lut, codes


@pytest.mark.parametrize("nq,n,m,k", [(5, 300, 4, 10), (1, 33, 8, 5),
                                      (8, 2050, 16, 7), (3, 64, 6, 10)])
def test_scoped_topk_pq_kernel_matches_oracle(nq, n, m, k):
    lut, codes = _pq_fixture(nq, n, m, seed=nq * n)
    mask = (np.random.default_rng(n).random(n) < 0.7)
    want_v, want_i = scoped_topk_pq_ref(lut, codes, mask, k=k)
    got_v, got_i = kops.scoped_topk_pq(lut, codes, mask, k=k)
    np.testing.assert_allclose(np.asarray(got_v), want_v, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


@pytest.mark.parametrize("nq,n,m,k", [(6, 500, 8, 10), (2, 96, 4, 5)])
def test_multi_scope_topk_pq_kernel_matches_oracle(nq, n, m, k):
    rng = np.random.default_rng(7)
    lut, codes = _pq_fixture(nq, n, m, seed=99)
    n_scopes = 3
    words = np.stack([pack_ids_to_words(
        np.flatnonzero(rng.random(n) < 0.6).astype(np.uint32), n)
        for _ in range(n_scopes)])
    sids = rng.integers(0, n_scopes, size=nq).astype(np.int32)
    want_v, want_i = multi_scope_topk_pq_ref(lut, codes, words, sids, k=k)
    got_v, got_i = kops.multi_scope_topk_pq(lut, codes, words, sids, k=k)
    np.testing.assert_allclose(np.asarray(got_v), want_v, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


def test_pq_kernel_empty_scope_and_all_masked():
    lut, codes = _pq_fixture(3, 256, 8, seed=1)
    mask = np.zeros(256, dtype=bool)
    v, i = kops.scoped_topk_pq(lut, codes, mask, k=5)
    assert (np.asarray(i) == -1).all()
    assert (np.asarray(v) <= np.finfo(np.float32).min).all()
    words = np.zeros((2, 256 // 32), dtype=np.uint32)
    sids = np.zeros(3, dtype=np.int32)
    v, i = kops.multi_scope_topk_pq(lut, codes, words, sids, k=5)
    assert (np.asarray(i) == -1).all()


def test_pq_kernel_scope_narrower_than_k():
    lut, codes = _pq_fixture(2, 128, 4, seed=2)
    mask = np.zeros(128, dtype=bool)
    mask[[5, 60]] = True
    v, i = kops.scoped_topk_pq(lut, codes, mask, k=10)
    got = np.asarray(i)
    assert set(got[got >= 0].tolist()) <= {5, 60}
    assert (got[:, 2:] == -1).all()


# --------------------------------------------------------------- executors
@pytest.mark.parametrize("metric", ["ip", "l2", "cos"])
def test_flat_pq_exhaustive_rescore_equals_fp32(metric):
    st = VectorStore(DIM, metric)
    st.add(RNG.normal(size=(1500, DIM)).astype(np.float32))
    ex = FlatExecutor(st)
    q = RNG.normal(size=(4, DIM)).astype(np.float32)
    sf, i_f = ex.search(q, 10)
    sp, ip_ = ex.search(q, 10, precision="pq", rescore_k=1500)
    np.testing.assert_array_equal(i_f, ip_)
    np.testing.assert_allclose(sf, sp, rtol=1e-4, atol=1e-4)


def test_flat_pq_gather_plans():
    st = VectorStore(DIM, "ip")
    st.add(RNG.normal(size=(4000, DIM)).astype(np.float32))
    ex = FlatExecutor(st)
    q = RNG.normal(size=(3, DIM)).astype(np.float32)
    small = np.arange(30, dtype=np.uint32)          # 30 <= rescore_k=40
    sf, i_f = ex.search(q, 10, candidate_ids=small)
    sp, ip_ = ex.search(q, 10, candidate_ids=small, precision="pq")
    np.testing.assert_array_equal(i_f, ip_)
    np.testing.assert_array_equal(sf, sp)           # identical fp32 launch
    big = np.arange(150, dtype=np.uint32)           # gather plan, > window
    spb, ipb = ex.search(q, 10, candidate_ids=big, precision="pq")
    assert set(ipb.ravel().tolist()) <= set(range(150))
    assert np.isfinite(spb).all()
    s, i = ex.search(q, 5, candidate_ids=np.empty(0, np.uint32),
                     precision="pq")
    assert (i == -1).all() and not np.isfinite(s).any()


def test_sharded_pq_exhaustive_rescore_equals_fp32():
    st = VectorStore(DIM, "ip")
    st.add(RNG.normal(size=(3000, DIM)).astype(np.float32))
    ex = ShardedExecutor(st)
    q = RNG.normal(size=(4, DIM)).astype(np.float32)
    scope = np.arange(0, 3000, 2, dtype=np.uint32)
    sf, i_f = ex.search(q, 10, candidate_ids=scope, plan="scan")
    sp, ip_ = ex.search(q, 10, candidate_ids=scope, plan="scan",
                        precision="pq", rescore_k=3000)
    np.testing.assert_array_equal(i_f, ip_)
    np.testing.assert_allclose(sf, sp, rtol=1e-4, atol=1e-4)


def test_tombstones_respected_by_pq_scan():
    db = DirectoryVectorDB(dim=DIM)
    db.ingest(RNG.normal(size=(600, DIM)).astype(np.float32), ["/x/"] * 600)
    db.build_ann("flat")
    q = RNG.normal(size=DIM).astype(np.float32)
    top = db.dsq(q, "/x/", k=5, precision="pq").ids[0]
    for eid in top[:2]:
        db.delete(int(eid))
    after = db.dsq(q, "/x/", k=5, precision="pq").ids[0]
    assert not (set(after.tolist()) & set(int(x) for x in top[:2]))


# ----------------------------------------------------- planner + accounting
def test_planner_precision_pq_per_group():
    # calibration=False: asserts hand-set planner internals (plan labels
    # depend on the hand-set gather threshold)
    db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi",
                           calibration=False)
    paths = ["/broad/"] * 900 + ["/narrow/"] * 20
    db.ingest(RNG.normal(size=(920, DIM)).astype(np.float32), paths)
    db.build_ann("flat")
    from repro.core.interface import normalize_batch
    acct = BatchAccounting()
    groups = db.planner().plan(db.namespaces["fs"], len(db.store),
                               normalize_batch(["/broad/", "/narrow/"], True,
                                               None),
                               k=10, acct=acct, precision="pq")
    by_path = {str(g.key.path): g for g in groups}
    broad = by_path[[p for p in by_path if "broad" in p][0]]
    narrow = by_path[[p for p in by_path if "narrow" in p][0]]
    assert broad.plan == "scan" and broad.precision == "pq"
    assert narrow.plan == "gather" and narrow.precision == "fp32"
    assert acct.precision_groups == {"pq": 1, "fp32": 1}


def test_batch_accounting_pq_terms_exclude_tombstones():
    # calibration=False: rescore_candidates == 6 * 40 assumes the hand-set
    # rescore factor
    db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi",
                           calibration=False)
    ids = db.ingest(RNG.normal(size=(1200, DIM)).astype(np.float32),
                    ["/a/"] * 600 + ["/b/"] * 600)
    db.build_ann("flat")
    m = db.store.pq_codebook.m
    q = RNG.normal(size=(6, DIM)).astype(np.float32)
    res = db.dsq_batch(q, ["/a/", "/b/", "/", "/a/", "/b/", "/"], k=10,
                       precision="pq")
    acct = res[0].batch
    assert acct.db_bytes_fp32 == 1200 * DIM * 4
    assert acct.db_bytes_pq == 1200 * m
    assert acct.db_bytes_pq <= 0.08 * acct.db_bytes_fp32
    assert acct.rescore_candidates == 6 * 40
    assert acct.precision_groups.get("pq") == 3
    for eid in ids[:30]:
        db.delete(int(eid))
    acct2 = db.dsq_batch(q, ["/a/"] * 6, k=10, precision="pq")[0].batch
    assert acct2.db_bytes_fp32 == 1170 * DIM * 4      # tombstones excluded
    assert acct2.db_bytes_pq == 1170 * m
    # default-precision batches carry no pq terms
    acct3 = db.dsq_batch(q, ["/a/"] * 6, k=10)[0].batch
    assert acct3.db_bytes_pq == 0 and acct3.rescore_fetch_bytes == 0
    assert "pq" not in acct3.precision_groups


def test_pq_rescore_window_grows_with_subspaces():
    """The default PQ window is 4k up to 16 subspaces and grows with
    sqrt(M) beyond (16k at the 1024-d default M = 256); the database
    applies it to dsq and dsq_batch alike, and an explicit window wins."""
    from repro.vectordb.quant import pq_rescore_factor
    assert [pq_rescore_factor(m) for m in (4, 16, 17, 64, 256)] == [
        4, 4, 8, 8, 16]
    db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi",
                           calibration=False, pq_m=DIM)     # M = 32: 8k
    db.ingest(RNG.normal(size=(1200, DIM)).astype(np.float32),
              ["/a/"] * 600 + ["/b/"] * 600)
    db.build_ann("flat")
    assert db.store.pq_m == DIM
    q = RNG.normal(size=(4, DIM)).astype(np.float32)
    res = db.dsq_batch(q, ["/a/", "/b/", "/", "/a/"], k=10, precision="pq")
    assert res[0].batch.rescore_candidates == 4 * 80
    for i, r in enumerate(res):
        loop = db.dsq(q[i], ["/a/", "/b/", "/", "/a/"][i], k=10,
                      precision="pq")
        np.testing.assert_array_equal(r.ids, loop.ids)
    acct = db.dsq_batch(q, ["/"] * 4, k=10, precision="pq",
                        rescore_k=40)[0].batch
    assert acct.rescore_candidates == 4 * 40


def test_dsq_still_rejects_unknown_precision():
    db = DirectoryVectorDB(dim=DIM)
    db.ingest(RNG.normal(size=(10, DIM)).astype(np.float32), ["/a/"] * 10)
    db.build_ann("flat")
    q = RNG.normal(size=DIM).astype(np.float32)
    with pytest.raises(ValueError, match="precision"):
        db.dsq(q, "/a/", precision="int4")
    with pytest.raises(ValueError, match="precision"):
        db.dsq_batch(q[None, :], ["/a/"], precision="fp16")


# ----------------------------------------------------------- tiered storage
def _tiered_db(n=2000, n_dirs=8):
    db = DirectoryVectorDB(dim=DIM, metric="ip")
    db.build_ann("flat")
    X = RNG.normal(size=(n, DIM)).astype(np.float32)
    db.ingest(X, [f"/d/{i % n_dirs}/" for i in range(n)])
    return db


def test_tiered_auto_upgrades_fp32_to_pq():
    db = _tiered_db()
    q = RNG.normal(size=(8, DIM)).astype(np.float32)
    paths = [f"/d/{i % 8}/" for i in range(8)]
    base = db.dsq_batch(q, paths, k=10)
    assert "pq" not in base[0].batch.precision_groups   # under budget: fp32
    assert base[0].batch.rows_host == 0
    db.store.set_device_budget(db.store.nbytes() // 4)
    assert db.store.tiered_active()
    res = db.dsq_batch(q, paths, k=10)                  # default precision
    acct = res[0].batch
    assert acct.precision_groups.get("pq", 0) > 0
    assert acct.rescore_fetch_bytes > 0
    assert acct.rows_device_pinned + acct.rows_host == 2000
    # approximate phase + exact rescore: high overlap with the fp32 answer
    rec = np.mean([len(set(a.ids[0]) & set(b.ids[0])) / 10
                   for a, b in zip(base, res)])
    assert rec >= 0.9


def test_tiered_hot_pinning_reduces_fetch():
    db = _tiered_db()
    q = RNG.normal(size=(8, DIM)).astype(np.float32)
    paths = [f"/d/{i % 8}/" for i in range(8)]
    db.store.set_device_budget(db.store.nbytes() // 3)
    a1 = db.dsq_batch(q, paths, k=10)[0].batch
    a2 = db.dsq_batch(q, paths, k=10)[0].batch
    assert a2.rows_device_pinned > 0                 # hot scopes pinned
    assert a2.rescore_fetch_bytes < a1.rescore_fetch_bytes


def test_store_pin_mask_survives_growth():
    """Regression: pin_rows sized the pinned mask to the buffer capacity at
    pin time, so ingest growth left a stale short mask — placement() raised
    a broadcast ValueError and rescore indexing raised IndexError."""
    store = VectorStore(dim=8, capacity=16)
    store.add(RNG.normal(size=(16, 8)).astype(np.float32))
    store.set_device_budget(1)
    store.pin_rows(np.arange(4))
    store.add(RNG.normal(size=(40, 8)).astype(np.float32))   # grows buffer
    dev, host = store.placement()                    # was: ValueError
    assert (dev, host) == (4, 52)
    pm = store.pinned_mask()
    assert pm.shape == (56,)
    assert bool(pm[55]) is False                     # was: IndexError
    assert pm[:4].all() and not pm[4:].any()


def test_tiered_survives_ingest_after_pin():
    """Pins taken before an ingest must not crash the next tiered batch —
    the DSM-era serving loop interleaves ingest with tiered DSQ."""
    db = _tiered_db(n=1500, n_dirs=4)
    db.store.set_device_budget(db.store.nbytes() // 3)
    q = RNG.normal(size=(4, DIM)).astype(np.float32)
    paths = [f"/d/{i % 4}/" for i in range(4)]
    db.dsq_batch(q, paths, k=5)                      # takes pins at n=1500
    assert db.store.pinned_mask().any()
    db.ingest(RNG.normal(size=(1200, DIM)).astype(np.float32),
              [f"/d/{i % 4}/" for i in range(1200)])  # grows past capacity
    pm = db.store.pinned_mask()
    assert pm.shape == (2700,) and not pm[1500:].any()   # new rows unpinned
    dev, host = db.store.placement()                 # no broadcast ValueError
    assert dev + host == 2700
    res = db.dsq_batch(q, paths, k=5)                # no IndexError in rescore
    acct = res[0].batch
    assert acct.rows_device_pinned + acct.rows_host == 2700


def test_tiered_cold_batch_keeps_hot_pins():
    """A batch over cold scopes must not unpin rows hotter scopes claimed in
    earlier batches (the cumulative-heat pin contract)."""
    db = _tiered_db(n=2000, n_dirs=8)
    db.store.set_device_budget(db.store.nbytes() // 3)
    q = RNG.normal(size=(8, DIM)).astype(np.float32)
    hot = ["/d/0/"] * 8
    for _ in range(3):                               # make /d/0/ clearly hot
        db.dsq_batch(q, hot, k=5)
    hot_pins = db.store.pinned_mask().copy()
    hot_ids = set(db.namespaces["fs"].resolve("/d/0/").to_array())
    assert hot_ids & set(np.flatnonzero(hot_pins))
    db.dsq_batch(q[:1], ["/d/7/"], k=5)              # one cold request
    still = set(np.flatnonzero(db.store.pinned_mask()))
    assert set(np.flatnonzero(hot_pins)) & hot_ids <= still


def test_pq_on_empty_store_raises_clear_error():
    store = VectorStore(dim=DIM)
    with pytest.raises(ValueError, match="not trained"):
        store.pq_lut(RNG.normal(size=(1, DIM)).astype(np.float32))
    cb = PQCodebook(DIM)
    with pytest.raises(ValueError, match="not trained"):
        cb.encode(RNG.normal(size=(2, DIM)).astype(np.float32))
    with pytest.raises(ValueError, match="not trained"):
        cb.decode(np.zeros((2, cb.m), np.uint8))


def test_tiered_results_match_explicit_pq():
    """The auto-upgraded plan is exactly the explicit precision="pq" plan."""
    db = _tiered_db()
    q = RNG.normal(size=(6, DIM)).astype(np.float32)
    paths = [f"/d/{i % 3}/" for i in range(6)]
    want = [r.ids.copy() for r in db.dsq_batch(q, paths, k=10,
                                               precision="pq")]
    db.store.set_device_budget(1)
    got = db.dsq_batch(q, paths, k=10)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g.ids)


def test_serving_surfaces_pq_and_tiered_stats():
    from repro.serving.rag import ContextDatabase, RAGConfig
    ctx = ContextDatabase(dim=DIM)
    for i in range(300):
        ctx.add_context(RNG.normal(size=DIM).astype(np.float32),
                        f"/docs/{i % 3}/", "L0", np.arange(4) + i)
    ctx.build("flat")
    cfg = RAGConfig(k=5, precision="pq")
    hits, stats = ctx.retrieve(RNG.normal(size=DIM).astype(np.float32),
                               "/docs/", cfg)
    assert len(hits) == 5
    assert stats["db_bytes_pq"] <= 0.08 * stats["db_bytes_fp32"]
    assert stats["rescore_candidates"] >= 20
    ctx.db.store.set_device_budget(ctx.db.store.nbytes() // 4)
    hits, stats = ctx.retrieve(RNG.normal(size=DIM).astype(np.float32),
                               "/docs/", RAGConfig(k=5))
    assert len(hits) == 5
    assert stats["rows_host"] > 0
    assert "rescore_fetch_bytes" in stats


def test_serving_tiered_stats_survive_full_pin_coverage():
    """Tiered stats are gated on tiered state, not on rows_host being
    nonzero — when the pin budget covers every alive row (rows_host == 0)
    the placement/fetch stats must still surface."""
    from repro.serving.rag import ContextDatabase, RAGConfig
    ctx = ContextDatabase(dim=DIM)
    eids = [ctx.add_context(RNG.normal(size=DIM).astype(np.float32),
                            f"/docs/{i % 3}/", "L0", np.arange(4) + i)
            for i in range(300)]
    ctx.build("flat")
    for eid in eids[20:]:          # tombstones: 20 alive rows, 300 buffered
        ctx.db.delete(eid)
    ctx.db.store.set_device_budget(ctx.db.store.nbytes() - 1)
    hits, stats = ctx.retrieve(RNG.normal(size=DIM).astype(np.float32),
                               "/docs/", RAGConfig(k=5))
    assert len(hits) == 5
    assert stats["rows_host"] == 0                   # everything fit pinned
    assert stats["rows_device_pinned"] == 20
    assert "rescore_fetch_bytes" in stats


# -------------------------------------------------------------- datasets
def test_dirgen_anchor_zipf_skews_scope_access():
    from repro.datasets.dirgen import make_wiki_dir
    flat = make_wiki_dir(scale=0.001, n_queries=200, seed=3)
    skew = make_wiki_dir(scale=0.001, n_queries=200, seed=3, anchor_zipf=1.5)
    # identical corpus (the knob only reshapes query traffic)
    np.testing.assert_array_equal(flat.vectors, skew.vectors)
    assert flat.entry_paths == skew.entry_paths

    def top_share(ds):
        from collections import Counter
        c = Counter(ds.query_anchors)
        return c.most_common(1)[0][1] / len(ds.query_anchors)

    assert top_share(skew) > top_share(flat)
