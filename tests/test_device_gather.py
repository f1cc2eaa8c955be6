"""The exact fp32 gather plan takes its candidate rows by id from the store
copy already on the device; a tiered store and the sharded tier's twin keep
the host gather. Both rank bit-identically, and the batch counters say
which one ran."""
import numpy as np
import pytest

from repro import tracing
from repro.vectordb import DirectoryVectorDB
from repro.vectordb.flat import FlatExecutor, _gather_topk_ids, bucket
from repro.vectordb.sharded import ShardedExecutor
from repro.vectordb.store import VectorStore

DIM = 24
K = 10


def _store(metric: str, n: int = 300, seed: int = 0) -> VectorStore:
    store = VectorStore(DIM, metric=metric)
    store.add(np.random.default_rng(seed).standard_normal(
        (n, DIM)).astype(np.float32))
    return store


def _gather(ex: FlatExecutor, queries, cand):
    with tracing.batch() as b:
        out = ex.search(queries, K, candidate_ids=cand, plan="gather")
    return out, b


@pytest.mark.parametrize("m", [1, K - 1, 64, 65])
@pytest.mark.parametrize("metric", ["ip", "cos", "l2"])
def test_device_gather_matches_host_gather(metric, m):
    store = _store(metric)
    rng = np.random.default_rng(m)
    cand = np.sort(rng.choice(len(store), m, replace=False)).astype(
        np.uint32)
    q = rng.standard_normal((3, DIM)).astype(np.float32)
    (s_dev, i_dev), b_dev = _gather(FlatExecutor(store), q, cand)
    (s_host, i_host), b_host = _gather(
        FlatExecutor(store, device_gather=False), q, cand)
    assert np.array_equal(s_dev, s_host)
    assert np.array_equal(i_dev, i_host)
    assert set(i_dev[i_dev >= 0].tolist()) <= set(cand.tolist())
    assert (i_dev >= 0).sum(axis=1).tolist() == [min(m, K)] * 3
    assert (b_dev.gather_rows_device, b_dev.gather_rows_host) == (m, 0)
    assert (b_host.gather_rows_device, b_host.gather_rows_host) == (0, m)
    # the device path hands over ids and their count, never rows
    assert b_dev.h2d_bytes == bucket(3) * DIM * 4 + bucket(m) * 4 + 4
    assert b_host.h2d_bytes >= bucket(m) * DIM * 4


@pytest.mark.parametrize("device_gather", [True, False])
def test_candidate_id_past_the_store_raises(device_gather):
    store = _store("ip", n=50)
    q = np.zeros((1, DIM), np.float32)
    with pytest.raises(IndexError):
        FlatExecutor(store, device_gather).search(
            q, K, candidate_ids=np.array([3, 50], np.uint32), plan="gather")


def _db(n_big: int = 2000) -> DirectoryVectorDB:
    """Gather scopes /g/<i>/ of 9, 16, 17 and 40 rows beside a broad /big/."""
    rng = np.random.default_rng(5)
    sizes = [9, 16, 17, 40]
    paths = [f"/g/{i}/" for i, s in enumerate(sizes) for _ in range(s)]
    paths += ["/big/"] * n_big
    db = DirectoryVectorDB(dim=DIM, scope_strategy="triehi")
    db.ingest(rng.standard_normal((len(paths), DIM)).astype(np.float32),
              paths)
    db.build_ann("flat")
    return db


@pytest.mark.parametrize("case", ["deleted_rows", "ingest_after_upload"])
def test_device_gather_follows_the_store(case):
    """Tombstoned rows leave the scope; rows ingested after the device copy
    was made are gathered from the refreshed copy."""
    db = _db()
    ex = db.executors["flat"]
    rng = np.random.default_rng(9)
    q = rng.standard_normal((2, DIM)).astype(np.float32)
    db.dsq(q, "/g/3/", k=K)                 # the device copy exists now
    scope = db.namespaces["fs"].resolve("/g/3/").to_array()
    if case == "deleted_rows":
        dead = [int(i) for i in scope[::3]]
        for eid in dead:
            db.delete(eid)
    else:
        new_ids = db.ingest(np.stack([q[0] * 100.0, q[1] * 100.0]),
                            ["/g/3/", "/g/3/"])
    dev = db.dsq(q, "/g/3/", k=K)
    ex.device_gather = False
    host = db.dsq(q, "/g/3/", k=K)
    assert np.array_equal(dev.scores, host.scores)
    assert np.array_equal(dev.ids, host.ids)
    got = set(dev.ids.ravel().tolist())
    if case == "deleted_rows":
        assert not got & set(dead)
        assert dev.scope_size == len(scope) - len(dead)
    else:
        assert dev.ids[0, 0] == new_ids[0] and dev.ids[1, 0] == new_ids[1]


@pytest.mark.parametrize("where", ["tiered", "sharded_twin"])
def test_rows_not_on_one_device_keep_the_host_gather(where):
    store = _store("ip", n=400)
    if where == "tiered":
        store.set_device_budget(1)
        assert store.tiered_active()
        ex = FlatExecutor(store)
    else:
        ex = ShardedExecutor(store).flat
    cand = np.arange(5, 45, dtype=np.uint32)
    q = np.random.default_rng(1).standard_normal((2, DIM)).astype(np.float32)
    (s, i), b = _gather(ex, q, cand)
    (s_dev, i_dev), _ = _gather(FlatExecutor(_store("ip", n=400)), q, cand)
    assert np.array_equal(s, s_dev) and np.array_equal(i, i_dev)
    assert (b.gather_rows_device, b.gather_rows_host) == (0, len(cand))
    assert b.h2d_bytes >= bucket(len(cand)) * DIM * 4


def test_sharded_batch_gathers_on_the_host():
    db = _db()
    db.build_ann("sharded")
    q = np.random.default_rng(2).standard_normal((4, DIM)).astype(np.float32)
    scopes = ["/g/0/", "/g/1/", "/g/2/", "/g/3/"]
    res = db.dsq_batch(q, scopes, k=K, executor="sharded")
    acct = res[0].batch
    assert acct.plan_groups == {"gather": 4}
    assert acct.gather_rows_device == 0
    assert acct.gather_rows_host == 9 + 16 + 17 + 40
    flat = db.dsq_batch(q, scopes, k=K, executor="flat")
    for a, b in zip(flat, res):
        assert np.array_equal(a.scores, b.scores)
        assert np.array_equal(a.ids, b.ids)


def test_one_compile_per_gather_bucket():
    """Two groups of one bucket share a compiled launch; the next bucket
    compiles once more."""
    store = _store("ip", n=333)            # a store size no other test uses
    ex = FlatExecutor(store)
    q = np.random.default_rng(4).standard_normal((2, DIM)).astype(np.float32)
    n0 = _gather_topk_ids._cache_size()
    ex.search(q, K, candidate_ids=np.arange(33, dtype=np.uint32),
              plan="gather")
    n1 = _gather_topk_ids._cache_size()
    ex.search(q, K, candidate_ids=np.arange(100, 160, dtype=np.uint32),
              plan="gather")
    n2 = _gather_topk_ids._cache_size()
    ex.search(q, K, candidate_ids=np.arange(65, dtype=np.uint32),
              plan="gather")
    assert (n1 - n0, n2 - n1, _gather_topk_ids._cache_size() - n2) == (
        1, 0, 1)


def test_gather_only_batch_uploads_ids_not_rows():
    """A flat dsq_batch of gather-only scopes hands over its queries, the
    padded candidate ids and one count per group: at most 8 bytes per
    candidate besides the queries, against 4 bytes per dimension per row
    when rows were uploaded."""
    db = _db()
    rng = np.random.default_rng(6)
    scopes = ["/g/0/", "/g/1/", "/g/1/", "/g/2/", "/g/3/", "/g/3/"]
    q = rng.standard_normal((len(scopes), DIM)).astype(np.float32)
    res = db.dsq_batch(q, scopes, k=K)
    acct = res[0].batch
    assert all(r.plan == "gather" for r in res)
    per_scope = {p: r.scope_size for p, r in zip(scopes, res)}
    candidates = sum(per_scope.values())
    assert acct.gather_rows_device == candidates == 9 + 16 + 17 + 40
    assert acct.gather_rows_host == 0
    queries = sum(bucket(scopes.count(p)) * DIM * 4 for p in per_scope)
    assert acct.h2d_bytes <= 8 * candidates + queries
    assert acct.h2d_bytes < candidates * DIM * 4
    loop = [db.dsq(q[j], p, k=K) for j, p in enumerate(scopes)]
    for a, b in zip(res, loop):
        assert np.array_equal(a.scores, b.scores)
        assert np.array_equal(a.ids, b.ids)
