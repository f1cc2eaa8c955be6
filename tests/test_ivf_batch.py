"""Device-resident batched IVF/PG executors.

Contract under test: the fused batched paths are *optimizations*, never
semantic changes — ``dsq_batch(executor="ivf"/"pg")`` is bit-identical to the
per-request ``dsq`` loop, the device IVF path matches the per-query host-loop
oracle, scoped recall holds against flat ground truth, DSM invalidates cached
scope masks on the IVF path, and tombstoned rows never surface from partition
lists or graph result sets.
"""
import numpy as np
import pytest

from repro.datasets import make_wiki_dir
from repro.vectordb import DirectoryVectorDB

DIM = 32


@pytest.fixture(scope="module")
def wiki():
    return make_wiki_dir(scale=0.0015, dim=DIM, n_queries=12, seed=5)


@pytest.fixture(scope="module")
def db(wiki):
    db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi")
    db.ingest(wiki.vectors, wiki.entry_paths)
    db.build_ann("flat")
    db.build_ann("ivf", n_lists=16)
    db.build_ann("pg", max_degree=10, ef_construction=24)
    return db


def _mixed(wiki, B):
    paths = [wiki.query_anchors[i % 4] for i in range(B)]
    paths[0] = "/"                              # one broad scope in the mix
    rec = [bool(wiki.query_recursive[i % 4]) for i in range(B)]
    return paths, rec


def _same_topk(ids_a, scores_a, ids_b, scores_b, msg=""):
    """Same member set per request + matching finite scores (tie order and
    numpy-vs-jnp low bits may differ between implementations)."""
    for b in range(ids_a.shape[0]):
        assert (set(ids_a[b][ids_a[b] >= 0].tolist())
                == set(ids_b[b][ids_b[b] >= 0].tolist())), (msg, b)
        np.testing.assert_allclose(
            np.sort(scores_a[b][np.isfinite(scores_a[b])]),
            np.sort(scores_b[b][np.isfinite(scores_b[b])]),
            rtol=1e-4, atol=1e-4, err_msg=f"{msg} {b}")


def test_batched_ivf_matches_loop_oracle(wiki, db):
    """Single-launch device path vs the per-query host-loop oracle: same ids
    and scores per request, scoped and unscoped."""
    ivf = db.executors["ivf"]
    q = wiki.queries.astype(np.float32)
    s1, i1 = ivf.search(q, 10, nprobe=6)
    s2, i2 = ivf.search_loop(q, 10, nprobe=6)
    _same_topk(i1, s1, i2, s2, "unscoped")
    cand = np.arange(0, len(db.store), 3, dtype=np.uint32)
    s1, i1 = ivf.search(q, 10, candidate_ids=cand, nprobe=6)
    s2, i2 = ivf.search_loop(q, 10, candidate_ids=cand, nprobe=6)
    _same_topk(i1, s1, i2, s2, "scoped")
    assert (i1[i1 >= 0] % 3 == 0).all()         # scope respected


def test_pallas_kernel_matches_jnp_twin(wiki, db):
    ivf = db.executors["ivf"]
    q = wiki.queries.astype(np.float32)
    n = len(db.store)
    mask = np.zeros(((n + 31) // 32) * 32, dtype=bool)
    mask[np.arange(0, n, 2)] = True
    words = np.packbits(mask, bitorder="little").view(np.uint32)[None, :]
    sids = np.zeros(len(q), np.int32)
    sa, ia = ivf.search_multi(q, words, sids, 10, nprobe=6, use_pallas=False)
    sb, ib = ivf.search_multi(q, words, sids, 10, nprobe=6, use_pallas=True)
    _same_topk(ia, sa, ib, sb, "pallas")


def test_pallas_list_store_follows_ingest_and_compaction(wiki):
    """The Pallas IVF scan reads a list-major copy of the rows: it must be
    rebuilt when ingest or compaction changes the lists (never serve stale
    rows), and match the jnp twin under l2 and with tombstones."""
    db = DirectoryVectorDB(dim=DIM, metric="l2", scope_strategy="triehi")
    db.ingest(wiki.vectors, wiki.entry_paths)
    db.build_ann("ivf", n_lists=8)
    ivf = db.executors["ivf"]
    q = wiki.queries.astype(np.float32)
    rng = np.random.default_rng(3)

    def check(msg):
        n = len(db.store)
        words = np.full((1, (n + 31) // 32), 0xFFFFFFFF, np.uint32)
        sids = np.zeros(len(q), np.int32)
        sa, ia = ivf.search_multi(q, words, sids, 10, nprobe=3)
        sb, ib = ivf.search_multi(q, words, sids, 10, nprobe=3,
                                  use_pallas=True)
        _same_topk(ia, sa, ib, sb, msg)
        assert (ib < n).all()

    check("built")
    before = ivf.list_blocks()
    new = db.ingest(q + 0.01 * rng.normal(size=q.shape).astype(np.float32),
                    ["/fresh/"] * len(q))
    check("after ingest")
    assert ivf.list_blocks() is not before
    assert set(np.asarray(new).tolist()) <= set(
        ivf.list_blocks().ids.ravel().tolist())
    for eid in new[: len(new) // 2]:
        db.delete(int(eid))
    check("tombstones")
    mapping = db.store.compact()
    assert mapping is not None
    ivf.remap_ids(mapping)
    check("after compaction")


@pytest.mark.parametrize("executor,params", [
    ("ivf", {"nprobe": 6}), ("ivf", {}), ("pg", {"ef_search": 32}),
    ("pg", {}),
])
def test_dsq_batch_equals_looped_dsq(wiki, db, executor, params):
    """Acceptance: dsq_batch matches the per-request dsq loop for both
    non-flat executors (default and plannable-param calls) — PG bit-identical,
    IVF same members/scores (batched dot_general low bits may differ with
    batch shape) — with one shared IVF launch and one PG traversal-mask build
    per unique scope."""
    B = len(wiki.queries)
    paths, rec = _mixed(wiki, B)
    batch = db.dsq_batch(wiki.queries, paths, k=10, recursive=rec,
                         executor=executor, **params)
    for i in range(B):
        r = db.dsq(wiki.queries[i], paths[i], k=10, recursive=rec[i],
                   executor=executor, **params)
        if executor == "pg":
            np.testing.assert_array_equal(batch[i].ids, r.ids,
                                          err_msg=str(i))
            np.testing.assert_array_equal(batch[i].scores, r.scores,
                                          err_msg=str(i))
        else:
            _same_topk(batch[i].ids, batch[i].scores, r.ids, r.scores,
                       f"req {i}")
        assert batch[i].scope_size == r.scope_size
        assert batch[i].plan == (executor if batch[i].scope_size else "empty")
    acct = batch[0].batch
    assert acct.batch_size == B
    assert acct.unique_scopes < B               # repeated scopes deduped
    if executor == "ivf":
        assert acct.launches == 1               # ONE fused launch, whole batch
    else:
        assert acct.launches == acct.unique_scopes


def test_dsq_batch_ivf_per_request_nprobe(wiki, db):
    """A per-request nprobe sequence groups launches by value and matches the
    loop with the respective nprobe."""
    B = 8
    paths, rec = _mixed(wiki, B)
    npr = [4] * 4 + [8] * 4
    batch = db.dsq_batch(wiki.queries[:B], paths, k=10, recursive=rec,
                         executor="ivf", nprobe=npr)
    assert batch[0].batch.launches == 2         # one per distinct nprobe
    for i in range(B):
        r = db.dsq(wiki.queries[i], paths[i], k=10, recursive=rec[i],
                   executor="ivf", nprobe=npr[i])
        _same_topk(batch[i].ids, batch[i].scores, r.ids, r.scores, str(i))


def test_dsq_batch_unplannable_params_still_fall_back(wiki, db):
    """An executor param the planner cannot plan must reach the executor via
    the per-request fallback, not be dropped."""
    with pytest.raises(TypeError):
        db.dsq_batch(wiki.queries[:2], ["/", "/"], k=5, executor="ivf",
                     bogus_param=1)


def test_scoped_ivf_recall_floor_vs_flat(wiki, db):
    """Batched IVF under directory scoping keeps recall vs the exact flat
    path on the dirgen dataset."""
    recalls = []
    for qi in range(len(wiki.queries)):
        exact = db.dsq(wiki.queries[qi], wiki.query_anchors[qi], k=10,
                       recursive=bool(wiki.query_recursive[qi]))
        want = set(exact.ids[0][exact.ids[0] >= 0].tolist())
        if not want:
            continue
        r = db.dsq(wiki.queries[qi], wiki.query_anchors[qi], k=10,
                   recursive=bool(wiki.query_recursive[qi]),
                   executor="ivf", nprobe=12)
        got = set(r.ids[0][r.ids[0] >= 0].tolist())
        recalls.append(len(got & want) / len(want))
    assert np.mean(recalls) >= 0.6, np.mean(recalls)


def _synthetic_db(n_top=5, per_dir=16, dim=16, seed=0):
    rng = np.random.default_rng(seed)
    paths = []
    for t in range(n_top):
        for j in range(per_dir):
            paths.append(f"/s{t}/" if j % 2 else f"/s{t}/inner/")
    vecs = rng.normal(size=(len(paths), dim)).astype(np.float32)
    db = DirectoryVectorDB(dim=dim, scope_strategy="triehi")
    db.ingest(vecs, paths)
    db.build_ann("ivf", n_lists=8)
    queries = rng.normal(size=(8, dim)).astype(np.float32)
    return db, queries


def test_ivf_cache_invalidation_after_move_merge():
    """Acceptance: DSM between identical batches must re-resolve on the IVF
    path exactly like per-request dsq — no stale cached masks."""
    db, queries = _synthetic_db()
    B = len(queries)
    paths = ["/s0/" if i % 2 == 0 else "/" for i in range(B)]
    before = db.dsq_batch(queries, paths, k=8, executor="ivf", nprobe=4)
    db.merge("/s0/", "/s1/")
    after = db.dsq_batch(queries, paths, k=8, executor="ivf", nprobe=4)
    for i in range(B):
        r = db.dsq(queries[i], paths[i], k=8, executor="ivf", nprobe=4)
        _same_topk(after[i].ids, after[i].scores, r.ids, r.scores, str(i))
        assert after[i].scope_size == r.scope_size
        if paths[i] == "/s0/":
            assert after[i].scope_size == 0 and before[i].scope_size > 0
    db.move("/s2/", "/s3/")
    post = db.dsq_batch(queries, ["/s3/"] * B, k=8, executor="ivf", nprobe=4)
    for i in range(B):
        r = db.dsq(queries[i], "/s3/", k=8, executor="ivf", nprobe=4)
        _same_topk(post[i].ids, post[i].scores, r.ids, r.scores, str(i))


def test_tombstones_mask_deleted_from_ivf_and_pg(wiki):
    """Deleted entries must never surface from IVF partition lists or PG
    result sets — including *unscoped* executor-level searches."""
    db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi")
    db.ingest(wiki.vectors, wiki.entry_paths)
    db.build_ann("ivf", n_lists=16)
    db.build_ann("pg", max_degree=10, ef_construction=24)
    q = wiki.queries[:4].astype(np.float32)
    _, ids0 = db.executors["ivf"].search(q, 10, nprobe=8)
    victims = [int(x) for x in ids0[0][ids0[0] >= 0][:3]]
    for v in victims:
        db.delete(v)
    assert db.store.n_deleted == len(victims)
    for name in ("ivf", "pg"):
        _, ids = db.executors[name].search(q, 10)      # unscoped probe
        assert not (set(victims) & set(ids.flatten().tolist())), name
    # batched DSQ path masks them too
    batch = db.dsq_batch(q, ["/"] * len(q), k=10, executor="ivf")
    got = {int(x) for r in batch for x in r.ids.flatten() if x >= 0}
    assert not (set(victims) & got)
    # oracle agrees
    _, ids = db.executors["ivf"].search_loop(q, 10)
    assert not (set(victims) & set(ids.flatten().tolist()))


def test_ivf_add_amortized_growth_keeps_membership(wiki):
    """Repeated small ingests route rows into capacity-grown lists without
    per-call concatenation; membership and search stay correct."""
    db = DirectoryVectorDB(dim=DIM)
    n0 = wiki.n_entries // 4
    db.ingest(wiki.vectors[:n0], wiki.entry_paths[:n0])
    db.build_ann("ivf", n_lists=8)
    step = max(1, (wiki.n_entries - n0) // 7)
    for lo in range(n0, wiki.n_entries, step):
        hi = min(lo + step, wiki.n_entries)
        db.ingest(wiki.vectors[lo:hi], wiki.entry_paths[lo:hi])
    ivf = db.executors["ivf"]
    members = np.sort(np.concatenate(ivf.lists))
    assert np.array_equal(members, np.arange(wiki.n_entries, dtype=np.uint32))
    r = db.dsq(wiki.queries[0], "/", k=10, executor="ivf", nprobe=8)
    assert (r.ids[0] >= 0).sum() == 10
    # layout rebuilt lazily after adds: sentinel must track the store size
    assert ivf.layout().n == len(db.store)
