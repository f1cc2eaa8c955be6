"""ShardedExecutor serving tier: plan parity with the flat path, the
device-resident scope table (token hits, DSM delta word-range patching),
incremental re-shard accounting, and the multi-scope dry-run specs.

Single-device cases run in-process (the executor degenerates to a 1-shard
mesh but exercises the full shard_map path); true multi-shard semantics run
in a subprocess with 8 simulated host devices (``multidevice`` marker, the
same pattern as ``test_distributed.py`` — the main pytest process must keep
seeing exactly 1 device)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.vectordb.parity import topk_mismatch

SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def _mixed_db(strategy="triehi", n=600, d=16, seed=0, calibration=None):
    from repro.vectordb import DirectoryVectorDB
    rng = np.random.default_rng(seed)
    paths = [f"/a/b{i % 7}/" if i % 3 else "/a/" for i in range(n)]
    db = DirectoryVectorDB(dim=d, scope_strategy=strategy,
                           calibration=calibration)
    db.ingest(rng.normal(size=(n, d)).astype(np.float32), paths)
    db.build_ann("flat")
    db.build_ann("sharded")
    return db, rng


def _assert_parity(res_a, res_b):
    """Sharded == flat: same ids (k-boundary ties aside) and scores within
    a few fp32 ULP — XLA rounds the shard-local matmul shape differently
    from the flat one, so bit-identity cannot hold across backends."""
    for a, b in zip(res_a, res_b):
        err = topk_mismatch(b.scores, b.ids, a.scores, a.ids)
        assert err is None, err
        assert a.scope_size == b.scope_size


@pytest.mark.parametrize("strategy", ["triehi", "pe_online", "pe_offline"])
def test_sharded_batch_matches_flat(strategy):
    db, rng = _mixed_db(strategy)
    B, d = 12, 16
    q = rng.normal(size=(B, d)).astype(np.float32)
    scopes = [["/a/", "/a/b1/", "/", "/a/b2/"][i % 4] for i in range(B)]
    rec = [bool(i % 3) for i in range(B)]
    exc = [["/a/b1/"] if i % 5 == 0 else [] for i in range(B)]
    _assert_parity(db.dsq_batch(q, scopes, k=5, recursive=rec, exclude=exc,
                                executor="flat"),
                   db.dsq_batch(q, scopes, k=5, recursive=rec, exclude=exc,
                                executor="sharded"))
    # per-request front door mirrors FlatExecutor.search too
    for i in range(B):
        a = db.dsq(q[i], scopes[i], k=5, executor="flat")
        b = db.dsq(q[i], scopes[i], k=5, executor="sharded")
        _assert_parity([a], [b])


def test_sharded_scope_table_hits_and_accounting():
    db, rng = _mixed_db()
    ex = db.executors["sharded"]
    B = 8
    q = rng.normal(size=(B, 16)).astype(np.float32)
    scopes = ["/a/", "/"] * (B // 2)
    r1 = db.dsq_batch(q, scopes, k=5, executor="sharded")
    acct = r1[0].batch
    assert acct.n_shards == ex.n_shards >= 1
    assert acct.shard_mask_bytes > 0          # first batch uploads the masks
    assert acct.collective_bytes > 0
    m0 = ex.mask_bytes_uploaded
    r2 = db.dsq_batch(q, scopes, k=5, executor="sharded")
    assert ex.mask_bytes_uploaded == m0       # token-validated slot hits
    assert r2[0].batch.shard_mask_hits == r2[0].batch.plan_groups.get("scan")
    assert r2[0].batch.shard_mask_bytes == 0


def test_sharded_table_grows_past_slot_capacity():
    """A batch with more unique scan scopes than table slots must grow the
    table (a same-batch LRU eviction would rank requests against the wrong
    mask) and stay bit-identical to flat."""
    db, rng = _mixed_db()
    db.build_ann("sharded", table_slots=2)
    ex = db.executors["sharded"]
    B = 12
    q = rng.normal(size=(B, 16)).astype(np.float32)
    paths = ["/"] * B
    exc = [[f"/a/b{i % 6}/"] for i in range(B)]   # 6 unique broad scopes
    _assert_parity(db.dsq_batch(q, paths, k=5, exclude=exc,
                                executor="flat"),
                   db.dsq_batch(q, paths, k=5, exclude=exc,
                                executor="sharded"))
    assert ex.table_slots >= 6


def test_sharded_dsm_delta_patches_resident_masks():
    db, rng = _mixed_db()
    ex = db.executors["sharded"]
    B = 8
    q = rng.normal(size=(B, 16)).astype(np.float32)
    db.dsq_batch(q, ["/a/", "/"] * (B // 2), k=5, executor="sharded")
    m0, p0 = ex.mask_bytes_uploaded, ex.masks_patched
    db.dsm_batch([("mkdir", "/z/"), ("move", "/a/b1/", "/z/")])
    # the /a/ and / slots lie on the vacated/gaining chains -> patched in
    # place with a word-range scatter, never re-uploaded
    assert ex.masks_patched > p0
    assert ex.mask_bytes_patched > 0
    _assert_parity(db.dsq_batch(q, ["/a/", "/"] * (B // 2), k=5,
                                executor="flat"),
                   db.dsq_batch(q, ["/a/", "/"] * (B // 2), k=5,
                                executor="sharded"))
    assert ex.mask_bytes_uploaded == m0, \
        "patched slots must be served without re-upload"


def test_sharded_view_incremental_resharding():
    db, rng = _mixed_db(n=600)
    ex = db.executors["sharded"]
    q = rng.normal(size=(4, 16)).astype(np.float32)
    db.dsq_batch(q, ["/"] * 4, k=5, executor="sharded")
    cap0, r0, b0 = ex.view.cap, ex.view.reshards, ex.view.db_bytes_uploaded
    # growth within padded capacity: only the new rows travel
    n_new = cap0 - len(db.store)
    assert n_new > 0
    db.ingest(rng.normal(size=(n_new, 16)).astype(np.float32),
              ["/a/"] * n_new)
    _assert_parity(db.dsq_batch(q, ["/", "/a/"] * 2, k=5, executor="flat"),
                   db.dsq_batch(q, ["/", "/a/"] * 2, k=5,
                                executor="sharded"))
    assert ex.view.reshards == r0
    assert ex.view.db_bytes_uploaded - b0 == n_new * 16 * 4
    # growth past capacity: one amortized-doubling re-shard
    db.ingest(rng.normal(size=(8, 16)).astype(np.float32), ["/a/"] * 8)
    _assert_parity(db.dsq_batch(q, ["/", "/a/"] * 2, k=5, executor="flat"),
                   db.dsq_batch(q, ["/", "/a/"] * 2, k=5,
                                executor="sharded"))
    assert ex.view.reshards == r0 + 1
    assert ex.view.cap == 2 * cap0


def test_sharded_alive_mask_patches_incrementally():
    """A tombstone must patch only the alive-mask words it touches, not
    rebuild/re-upload the whole packed mask."""
    db, rng = _mixed_db()
    ex = db.executors["sharded"]
    q = rng.normal(size=(4, 16)).astype(np.float32)
    db.dsq_batch(q, ["/"] * 4, k=5, executor="sharded")
    full = ex.view.n_words * 4
    a0 = ex.view.alive_bytes_uploaded
    assert a0 >= full                      # initial full upload happened
    db.delete(1)
    _assert_parity(db.dsq_batch(q, ["/"] * 4, k=5, executor="flat"),
                   db.dsq_batch(q, ["/"] * 4, k=5, executor="sharded"))
    delta = ex.view.alive_bytes_uploaded - a0
    assert 0 < delta < full, (delta, full)


def test_sharded_tombstones_and_rmdir():
    db, rng = _mixed_db()
    q = rng.normal(size=(6, 16)).astype(np.float32)
    db.delete(0)
    db.delete(5)
    db.rmdir("/a/b3/")
    scopes = ["/", "/a/", "/a/b1/"] * 2
    _assert_parity(db.dsq_batch(q, scopes, k=5, executor="flat"),
                   db.dsq_batch(q, scopes, k=5, executor="sharded"))
    for r in db.dsq_batch(q, scopes, k=20, executor="sharded"):
        ids = r.ids[r.ids >= 0]
        assert 0 not in ids and 5 not in ids


def test_sharded_serving_rag_parity():
    from repro.serving.rag import ContextDatabase, RAGConfig
    rng = np.random.default_rng(3)
    d = 16
    ctx = ContextDatabase(dim=d)
    for i in range(120):
        path = f"/mem/s{i % 5}/" if i % 2 else "/mem/"
        vec = rng.normal(size=d).astype(np.float32)
        ctx.add_context(vec, path, "L0", np.arange(4, dtype=np.int32))
    ctx.build("flat")
    ctx.build("sharded")
    q = rng.normal(size=(4, d)).astype(np.float32)
    scopes = ["/mem/", "/mem/s1/", "/mem/", "/mem/s2/"]
    flat = ctx.retrieve_batch(q, scopes, RAGConfig(k=5, executor="flat"))
    shard = ctx.retrieve_batch(q, scopes, RAGConfig(k=5, executor="sharded"))
    for (ha, _), (hb, sb) in zip(flat, shard):
        assert [h.entry_id for h in ha] == [h.entry_id for h in hb]
        assert sb["n_shards"] >= 1
        assert "collective_bytes" in sb


def test_multi_scope_input_specs_shapes():
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.distributed.search import multi_scope_search_input_specs
    mesh = make_mesh((1,), ("data",))
    (db, words, alive, sids, q), shardings = multi_scope_search_input_specs(
        mesh, n_total=256, dim=32, n_queries=6, n_scopes=3)
    assert db.shape == (256, 32) and db.dtype == jnp.float32
    assert words.shape == (3, 8) and words.dtype == jnp.uint32
    assert alive.shape == (8,) and alive.dtype == jnp.uint32
    assert sids.shape == (6,) and sids.dtype == jnp.int32
    assert q.shape == (6, 32) and q.dtype == jnp.float32
    assert len(shardings) == 5
    with pytest.raises(AssertionError):
        multi_scope_search_input_specs(mesh, n_total=100, dim=32,
                                       n_queries=6, n_scopes=3)


def test_dryrun_sharded_scan_lowers():
    """The batched sharded scan lowers/compiles from specs alone (the
    launch/dryrun.py viking-scan-batch path, at toy size on 1 device)."""
    import jax
    from repro.launch.mesh import make_mesh
    from repro.distributed.search import (make_sharded_batch_search,
                                          multi_scope_search_input_specs)
    mesh = make_mesh((1,), ("data",))
    fn = make_sharded_batch_search(mesh, 256, 32, 10)
    args, shardings = multi_scope_search_input_specs(mesh, 256, 32, 6, 3)
    with mesh:
        compiled = jax.jit(fn.__wrapped__ if hasattr(fn, "__wrapped__")
                           else fn, in_shardings=shardings).lower(
            *args).compile()
    assert compiled is not None


def test_sharded_int8_two_phase_matches_flat_int8():
    """sharded int8 (shard-local int8 scan + shard-merge + one global fp32
    rescore) must return the same top-k sets and fp32 scores as the flat
    int8 path, and — with an exhaustive rescore window — the exact fp32
    result."""
    # calibration=False: the quantized-byte accounting assumes the int8
    # request is not measured-upgraded to fp32
    db, rng = _mixed_db(calibration=False)
    B, d = 8, 16
    q = rng.normal(size=(B, d)).astype(np.float32)
    scopes = [["/a/", "/", "/a/b2/"][i % 3] for i in range(B)]
    exact = db.dsq_batch(q, scopes, k=5, executor="sharded")
    for rk in (64, len(db.store)):
        sh = db.dsq_batch(q, scopes, k=5, executor="sharded",
                          precision="int8", rescore_k=rk)
        fl = db.dsq_batch(q, scopes, k=5, executor="flat",
                          precision="int8", rescore_k=rk)
        for a, b in zip(sh, fl):
            assert (set(int(x) for x in a.ids[0])
                    == set(int(x) for x in b.ids[0]))
            np.testing.assert_allclose(np.sort(a.scores[0]),
                                       np.sort(b.scores[0]),
                                       rtol=1e-5, atol=1e-5)
    for a, b in zip(sh, exact):
        assert (set(int(x) for x in a.ids[0])
                == set(int(x) for x in b.ids[0]))
    acct = sh[0].batch
    assert acct.db_bytes_int8 and acct.rescore_candidates


# --------------------------------------------------------------- multidevice
@pytest.mark.multidevice
def test_sharded_int8_8dev():
    """8-shard int8 scan: per-shard top-r merge + global rescore equals the
    fp32 exact result under an exhaustive window, tombstones stay masked."""
    run_with_devices("""
        import numpy as np
        from repro.vectordb import DirectoryVectorDB
        rng = np.random.default_rng(5)
        db = DirectoryVectorDB(dim=16, scope_strategy="triehi")
        paths = [f"/a/b{i % 5}/" if i % 2 else "/c/" for i in range(900)]
        db.ingest(rng.normal(size=(900, 16)).astype(np.float32), paths)
        db.build_ann("flat")
        db.build_ann("sharded")
        assert db.executors["sharded"].n_shards == 8
        q = rng.normal(size=(6, 16)).astype(np.float32)
        scopes = [["/a/", "/", "/c/"][i % 3] for i in range(6)]
        exact = db.dsq_batch(q, scopes, k=5, executor="sharded")
        sh = db.dsq_batch(q, scopes, k=5, executor="sharded",
                          precision="int8", rescore_k=900)
        for a, b in zip(sh, exact):
            assert (set(int(x) for x in a.ids[0])
                    == set(int(x) for x in b.ids[0])), (a.ids, b.ids)
        # tombstoned rows never resurface from the int8 mesh scan
        dead = [int(x) for x in exact[1].ids[0][:2]]
        for eid in dead:
            db.delete(eid)
        after = db.dsq_batch(q, scopes, k=5, executor="sharded",
                             precision="int8", rescore_k=900)
        got = {int(x) for r in after for x in r.ids[0]}
        assert not (got & set(dead))
        print("ok")
    """)


@pytest.mark.multidevice
def test_sharded_batch_bit_identical_8dev():
    """The acceptance contract: on an 8-host-device mesh, dsq_batch
    executor='sharded' is bit-identical to the single-device flat batch
    path, including immediately after a dsm_batch of move/merge/remove ops
    with the shard-resident masks patched (not rebuilt)."""
    run_with_devices("""
        import numpy as np, jax
        from repro.vectordb import DirectoryVectorDB
        assert len(jax.devices()) == 8
        rng = np.random.default_rng(1)
        n, d, B = 2000, 32, 24
        paths = [f"/w/p{i%9}/" if i % 4 else "/w/" for i in range(n)]
        db = DirectoryVectorDB(dim=d, scope_strategy="triehi")
        db.ingest(rng.normal(size=(n, d)).astype(np.float32), paths)
        db.build_ann("flat"); db.build_ann("sharded")
        ex = db.executors["sharded"]
        assert ex.n_shards == 8
        q = rng.normal(size=(B, d)).astype(np.float32)
        scopes = [["/w/", "/w/p1/", "/", "/w/p3/", "/w/p4/"][i % 5]
                  for i in range(B)]
        rec = [bool(i % 3) for i in range(B)]
        rf = db.dsq_batch(q, scopes, k=10, recursive=rec, executor="flat")
        rs = db.dsq_batch(q, scopes, k=10, recursive=rec, executor="sharded")
        for a, b in zip(rf, rs):
            assert np.array_equal(a.scores, b.scores)
            assert np.array_equal(a.ids, b.ids)
        # DSM: shard-resident masks patch in place, results stay identical
        m0 = ex.mask_bytes_uploaded
        db.dsm_batch([("mkdir", "/x/"), ("move", "/w/p1/", "/x/"),
                      ("merge", "/w/p3/", "/w/p4/"), ("remove", "/w/p5/")])
        rf = db.dsq_batch(q, ["/w/", "/"] * (B // 2), k=10, executor="flat")
        rs = db.dsq_batch(q, ["/w/", "/"] * (B // 2), k=10,
                          executor="sharded")
        for a, b in zip(rf, rs):
            assert np.array_equal(a.scores, b.scores)
            assert np.array_equal(a.ids, b.ids)
        assert ex.masks_patched >= 1
        assert ex.mask_bytes_uploaded == m0, "survivors must not re-upload"
        print("8dev bit-identity OK", ex.stats())
    """)


@pytest.mark.multidevice
def test_sharded_ingest_reshard_8dev():
    run_with_devices("""
        import numpy as np, jax
        from repro.vectordb import DirectoryVectorDB
        rng = np.random.default_rng(7)
        d = 16
        db = DirectoryVectorDB(dim=d)
        db.ingest(rng.normal(size=(300, d)).astype(np.float32), ["/a/"] * 300)
        db.build_ann("flat"); db.build_ann("sharded")
        ex = db.executors["sharded"]
        q = rng.normal(size=(4, d)).astype(np.float32)
        db.dsq_batch(q, ["/"] * 4, k=5, executor="sharded")
        assert ex.view.cap % (32 * 8) == 0
        cap0, r0 = ex.view.cap, ex.view.reshards
        grow = cap0 - len(db.store)
        db.ingest(rng.normal(size=(grow, d)).astype(np.float32),
                  ["/a/"] * grow)
        rf = db.dsq_batch(q, ["/"] * 4, k=5, executor="flat")
        rs = db.dsq_batch(q, ["/"] * 4, k=5, executor="sharded")
        for a, b in zip(rf, rs):
            assert np.array_equal(a.scores, b.scores)
            assert np.array_equal(a.ids, b.ids)
        assert ex.view.reshards == r0          # in-place row scatter
        db.ingest(rng.normal(size=(1, d)).astype(np.float32), ["/a/"])
        db.dsq_batch(q, ["/"] * 4, k=5, executor="sharded")
        assert ex.view.reshards == r0 + 1      # amortized-doubling re-shard
        assert ex.view.cap == 2 * cap0
        print("8dev reshard OK")
    """)


@pytest.mark.multidevice
def test_dryrun_sharded_scan_compiles_8dev():
    run_with_devices("""
        import jax
        from repro.launch.mesh import make_mesh_for_devices
        from repro.distributed.search import (make_sharded_batch_search,
                                              multi_scope_search_input_specs)
        mesh = make_mesh_for_devices(model_parallelism=2)
        fn = make_sharded_batch_search(mesh, 2048, 64, 10)
        args, shardings = multi_scope_search_input_specs(mesh, 2048, 64, 8, 4)
        with mesh:
            compiled = jax.jit(
                fn.__wrapped__ if hasattr(fn, "__wrapped__") else fn,
                in_shardings=shardings).lower(*args).compile()
        print("sharded scan dry-run OK")
    """)
