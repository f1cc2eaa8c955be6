"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("q,n,d,k,metric,dtype", [
    (1, 128, 32, 4, "ip", np.float32),
    (3, 1000, 64, 10, "ip", np.float32),
    (8, 4096, 128, 10, "l2", np.float32),
    (5, 2048, 256, 16, "l2", np.float32),
    (2, 777, 128, 8, "ip", jnp.bfloat16),
    (16, 512, 512, 32, "ip", np.float32),
])
def test_scoped_topk_sweep(q, n, d, k, metric, dtype):
    Q = RNG.normal(size=(q, d)).astype(np.float32)
    X = jnp.asarray(RNG.normal(size=(n, d)).astype(np.float32), dtype=dtype)
    mask = RNG.random(n) < 0.4
    v1, i1 = ops.scoped_topk(Q, X, mask, k=k, metric=metric)
    v2, i2 = ref.scoped_topk_ref(jnp.asarray(Q), X, jnp.asarray(mask),
                                 k=k, metric=metric)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=tol, atol=tol)
    # ids must point at valid candidates with matching scores
    for qi in range(q):
        for slot in range(k):
            idx = int(i1[qi, slot])
            if idx >= 0:
                assert mask[idx]


@pytest.mark.parametrize("q,n,d,k,metric,n_scopes", [
    (1, 128, 32, 4, "ip", 1),
    (5, 1000, 64, 10, "ip", 3),
    (8, 777, 128, 7, "l2", 4),
    (16, 2048, 256, 16, "l2", 5),
])
def test_multi_scope_topk_sweep(q, n, d, k, metric, n_scopes):
    """Single-launch heterogeneous batch: per-query scope-id indirection into
    a packed (n_scopes, n/32) mask matrix must match the unfused oracle."""
    Q = RNG.normal(size=(q, d)).astype(np.float32)
    X = RNG.normal(size=(n, d)).astype(np.float32)
    dense = RNG.random((n_scopes, n)) < 0.4
    pad = (-n) % 32
    words = np.stack([
        np.packbits(np.pad(m, (0, pad)), bitorder="little").view(np.uint32)
        for m in dense])
    sid = RNG.integers(0, n_scopes, size=q).astype(np.int32)
    v1, i1 = ops.multi_scope_topk(Q, X, words, sid, k=k, metric=metric)
    v2, i2 = ref.multi_scope_topk_ref(jnp.asarray(Q), jnp.asarray(X),
                                      jnp.asarray(words), jnp.asarray(sid),
                                      k=k, metric=metric)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                               rtol=1e-4, atol=1e-4)
    for qi in range(q):
        for slot in range(k):
            idx = int(i1[qi, slot])
            if idx >= 0:
                assert dense[sid[qi], idx], (qi, slot, idx)


def test_multi_scope_topk_degenerates_to_scoped_topk():
    """With one scope shared by every query, the multi-scope kernel must
    reproduce the single-scope kernel exactly."""
    Q = RNG.normal(size=(4, 64)).astype(np.float32)
    X = RNG.normal(size=(512, 64)).astype(np.float32)
    mask = RNG.random(512) < 0.3
    words = np.packbits(mask, bitorder="little").view(np.uint32)[None, :]
    sid = np.zeros(4, np.int32)
    v1, i1 = ops.multi_scope_topk(Q, X, words, sid, k=8)
    v2, i2 = ops.scoped_topk(Q, X, mask, k=8)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)


def test_multi_scope_topk_empty_scope_row():
    """A scope with zero candidates yields all -1 ids for its queries while
    other scopes in the same launch are unaffected."""
    Q = RNG.normal(size=(2, 32)).astype(np.float32)
    X = RNG.normal(size=(256, 32)).astype(np.float32)
    full = np.ones(256, bool)
    words = np.stack([
        np.zeros(8, np.uint32),
        np.packbits(full, bitorder="little").view(np.uint32)])
    sid = np.array([0, 1], np.int32)
    v, i = ops.multi_scope_topk(Q, X, words, sid, k=4)
    assert (np.asarray(i)[0] == -1).all()
    assert (np.asarray(i)[1] >= 0).all()


def _quantize(rows):
    from repro.vectordb.quant import quantize_rows
    return quantize_rows(rows)


def _q_norms(codes, scales):
    c = codes.astype(np.int32)
    return np.einsum("nd,nd->n", c, c).astype(np.float32) * scales * scales


@pytest.mark.parametrize("q,n,d,k,metric,block_q,block_n", [
    (1, 128, 32, 4, "ip", 8, 1024),
    (3, 1000, 64, 10, "ip", 8, 1024),
    (8, 4096, 128, 10, "l2", 8, 1024),
    (5, 2048, 256, 16, "l2", 4, 512),
    (16, 512, 512, 32, "ip", 8, 128),
    (2, 777, 128, 1, "ip", 2, 256),
])
def test_scoped_topk_i8_sweep(q, n, d, k, metric, block_q, block_n):
    """int8 scan kernel vs the numpy oracle across block shapes and k: the
    int32-accumulated code dot with merge-time scales must match the oracle
    bitwise on scores (both compute the identical fp32 products)."""
    Q = RNG.normal(size=(q, d)).astype(np.float32)
    X = RNG.normal(size=(n, d)).astype(np.float32)
    q_i8, q_s = _quantize(Q)
    x_i8, x_s = _quantize(X)
    sq = _q_norms(x_i8, x_s)
    mask = RNG.random(n) < 0.4
    v1, i1 = ops.scoped_topk_i8(q_i8, q_s, x_i8, x_s, sq, mask, k=k,
                                metric=metric, block_q=block_q,
                                block_n=block_n)
    v2, i2 = ref.scoped_topk_i8_ref(q_i8, q_s, x_i8, x_s, sq, mask, k=k,
                                    metric=metric)
    np.testing.assert_allclose(np.asarray(v1), v2, rtol=1e-6, atol=1e-6)
    for qi in range(q):
        for slot in range(k):
            idx = int(i1[qi, slot])
            if idx >= 0:
                assert mask[idx], (qi, slot, idx)
            else:
                assert v2[qi, slot] <= ref.NEG_INF


@pytest.mark.parametrize("q,n,d,k,metric,n_scopes", [
    (1, 128, 32, 4, "ip", 1),
    (5, 1000, 64, 10, "ip", 3),
    (8, 777, 128, 7, "l2", 4),
    (16, 2048, 256, 16, "l2", 5),
])
def test_multi_scope_topk_i8_sweep(q, n, d, k, metric, n_scopes):
    """Heterogeneous-batch int8 kernel vs the numpy oracle: packed-word
    scope indirection over the quantized store."""
    Q = RNG.normal(size=(q, d)).astype(np.float32)
    X = RNG.normal(size=(n, d)).astype(np.float32)
    q_i8, q_s = _quantize(Q)
    x_i8, x_s = _quantize(X)
    sq = _q_norms(x_i8, x_s)
    dense = RNG.random((n_scopes, n)) < 0.4
    pad = (-n) % 32
    words = np.stack([
        np.packbits(np.pad(m, (0, pad)), bitorder="little").view(np.uint32)
        for m in dense])
    sid = RNG.integers(0, n_scopes, size=q).astype(np.int32)
    v1, i1 = ops.multi_scope_topk_i8(q_i8, q_s, x_i8, x_s, sq, words, sid,
                                     k=k, metric=metric)
    v2, i2 = ref.multi_scope_topk_i8_ref(q_i8, q_s, x_i8, x_s, sq, words,
                                         sid, k=k, metric=metric)
    np.testing.assert_allclose(np.asarray(v1), v2, rtol=1e-6, atol=1e-6)
    for qi in range(q):
        for slot in range(k):
            idx = int(i1[qi, slot])
            if idx >= 0:
                assert dense[sid[qi], idx], (qi, slot, idx)


def test_multi_scope_topk_i8_empty_scope_row():
    """A scope with zero candidates yields all -1 for its queries while
    other scopes in the same int8 launch are unaffected."""
    Q = RNG.normal(size=(2, 32)).astype(np.float32)
    X = RNG.normal(size=(256, 32)).astype(np.float32)
    q_i8, q_s = _quantize(Q)
    x_i8, x_s = _quantize(X)
    sq = _q_norms(x_i8, x_s)
    words = np.stack([
        np.zeros(8, np.uint32),
        np.packbits(np.ones(256, bool), bitorder="little").view(np.uint32)])
    sid = np.array([0, 1], np.int32)
    v, i = ops.multi_scope_topk_i8(q_i8, q_s, x_i8, x_s, sq, words, sid, k=4)
    assert (np.asarray(i)[0] == -1).all()
    assert (np.asarray(i)[1] >= 0).all()


def test_scoped_topk_i8_all_masked_tiles():
    """Whole blocks masked out (and the fully-empty mask) never surface a
    candidate — the merge must ignore all-masked tiles entirely."""
    Q = RNG.normal(size=(2, 64)).astype(np.float32)
    X = RNG.normal(size=(1024, 64)).astype(np.float32)
    q_i8, q_s = _quantize(Q)
    x_i8, x_s = _quantize(X)
    sq = _q_norms(x_i8, x_s)
    v, i = ops.scoped_topk_i8(q_i8, q_s, x_i8, x_s, sq,
                              np.zeros(1024, bool), k=4, block_n=256)
    assert (np.asarray(i) == -1).all()
    # only the last block carries candidates: ids must all land there
    mask = np.zeros(1024, bool)
    mask[768:] = True
    v, i = ops.scoped_topk_i8(q_i8, q_s, x_i8, x_s, sq, mask, k=8,
                              block_n=256)
    i = np.asarray(i)
    assert (i >= 768).all()
    v2, i2 = ref.scoped_topk_i8_ref(q_i8, q_s, x_i8, x_s, sq, mask, k=8)
    np.testing.assert_allclose(np.asarray(v), v2, rtol=1e-6, atol=1e-6)


def test_scoped_topk_i8_matches_fp32_ranking():
    """The int8 scan's top-k set approximates the fp32 kernel's: with a
    4x-rescore-sized k every fp32 top-k member must appear (the recall
    contract the two-phase plan relies on)."""
    Q = RNG.normal(size=(4, 64)).astype(np.float32)
    X = RNG.normal(size=(2048, 64)).astype(np.float32)
    q_i8, q_s = _quantize(Q)
    x_i8, x_s = _quantize(X)
    sq = _q_norms(x_i8, x_s)
    mask = np.ones(2048, bool)
    vf, idf = ops.scoped_topk(Q, X, mask, k=10)
    v8, id8 = ops.scoped_topk_i8(q_i8, q_s, x_i8, x_s, sq, mask, k=40)
    idf, id8 = np.asarray(idf), np.asarray(id8)
    for qi in range(4):
        assert set(idf[qi].tolist()) <= set(id8[qi].tolist())


def _list_major(n_rows, d, list_lens, block):
    """A random store laid out list-major: list c holds ``list_lens[c]``
    distinct row ids, padded to whole blocks (at least one) with id -1."""
    X = RNG.normal(size=(n_rows, d)).astype(np.float32)
    members = RNG.permutation(n_rows)
    nblk = np.maximum(1, -(-np.asarray(list_lens) // block))
    start = np.concatenate([[0], np.cumsum(nblk)[:-1]])
    ids = np.full((int(nblk.sum()), block), -1, np.int32)
    flat, lo = ids.reshape(-1), 0
    for c, ln in enumerate(list_lens):
        flat[start[c] * block: start[c] * block + ln] = members[lo: lo + ln]
        lo += ln
    rows = np.where(ids.reshape(-1, 1) >= 0, X[np.maximum(ids, 0).reshape(-1)],
                    0.0).astype(np.float32)
    return X, rows, ids, start.astype(np.int32), nblk.astype(np.int32)


@pytest.mark.parametrize("b,c,d,k,metric,density", [
    (1, 128, 32, 4, "ip", 0.5),
    (4, 640, 64, 10, "ip", 0.3),
    (3, 1024, 128, 8, "l2", 0.7),
    (8, 333, 64, 16, "l2", 0.2),
])
def test_ivf_gather_topk_sweep(b, c, d, k, metric, density):
    """Batched-IVF list scan: each query streams its probed lists (``c``
    rows over 6 lists of uneven length, one empty) from the list-major
    store, masks by its packed scope words, and must match the unfused
    numpy oracle exactly — ids and tie order included."""
    block = 32
    cuts = np.sort(RNG.choice(np.arange(1, c), size=4, replace=False))
    lens = np.diff(np.concatenate([[0], cuts, [c]])).tolist() + [0]
    n = c + 7                                   # rows in no list
    X, rows, ids, start, nblk = _list_major(n, d, lens, block)
    sq = np.where(ids >= 0, np.sum(X * X, axis=1)[np.maximum(ids, 0)], 0.0)
    Q = RNG.normal(size=(b, d)).astype(np.float32)
    probe = np.stack([RNG.permutation(len(lens))[:4] for _ in range(b)])
    dense = RNG.random((b, n)) < density
    pad = (-n) % 32
    qwords = np.stack([
        np.packbits(np.pad(m, (0, pad)), bitorder="little").view(np.uint32)
        for m in dense])
    v1, i1 = ops.ivf_gather_topk(Q, probe, start, nblk, rows, ids, sq,
                                 qwords, max_blocks=int(nblk.max()), k=k,
                                 metric=metric)
    v2, i2 = ref.ivf_gather_topk_ref(Q, probe, start, nblk, rows, ids,
                                     qwords, k=k, metric=metric)
    np.testing.assert_array_equal(np.asarray(i1), i2)
    np.testing.assert_allclose(np.asarray(v1), v2, rtol=1e-5, atol=1e-5)
    for qi in range(b):
        probed = set(ids[np.concatenate([
            np.arange(start[p], start[p] + nblk[p]) for p in probe[qi]])]
            .reshape(-1).tolist()) - {-1}
        for idx in np.asarray(i1)[qi]:
            assert idx < 0 or (idx in probed and dense[qi, idx])


def test_ivf_gather_topk_all_padding_row():
    """A query whose probed lists hold nothing in its scope yields all -1;
    one whose scope is everything fills its k slots."""
    lens = [0, 40, 24]
    X, rows, ids, start, nblk = _list_major(64, 32, lens, 32)
    Q = RNG.normal(size=(2, 32)).astype(np.float32)
    probe = np.array([[0, 1], [1, 2]])
    qwords = np.stack([np.zeros(2, np.uint32),
                       np.full(2, 0xFFFFFFFF, np.uint32)])
    v, i = ops.ivf_gather_topk(Q, probe, start, nblk, rows, ids,
                               np.zeros(ids.shape, np.float32), qwords,
                               max_blocks=int(nblk.max()), k=4)
    assert (np.asarray(i)[0] == -1).all()
    assert (np.asarray(i)[1] >= 0).all()


def test_scoped_topk_empty_and_full_mask():
    Q = RNG.normal(size=(2, 64)).astype(np.float32)
    X = RNG.normal(size=(256, 64)).astype(np.float32)
    v, i = ops.scoped_topk(Q, X, np.zeros(256, bool), k=4)
    assert (np.asarray(i) == -1).all()
    v, i = ops.scoped_topk(Q, X, np.ones(256, bool), k=4)
    vr, ir = ref.scoped_topk_ref(jnp.asarray(Q), jnp.asarray(X),
                                 jnp.ones(256, bool), k=4)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4000), st.integers(0, 2 ** 32 - 1))
def test_bitmap_popcount_property(n, seed):
    r = np.random.default_rng(seed)
    a = r.integers(0, 2 ** 32, size=n, dtype=np.uint32)
    b = r.integers(0, 2 ** 32, size=n, dtype=np.uint32)
    w1, c1 = ops.mask_and_popcount(a, b)
    w2, c2 = ref.mask_and_popcount_ref(jnp.asarray(a), jnp.asarray(b))
    assert np.array_equal(np.asarray(w1), np.asarray(w2))
    assert int(c1) == int(c2)
    # oracle-of-oracle: numpy bit_count
    assert int(c1) == int(np.bitwise_count(a & b).sum())


@pytest.mark.parametrize("rows,n_words", [
    (1, 8), (4, 64), (7, 333), (16, 2048), (3, 4097),
])
def test_bitmap_patch_sweep(rows, n_words):
    """Batched mask patch (the DSM delta-maintenance primitive): Pallas
    kernel vs jnp twin vs numpy oracle, mixed OR/AND-NOT/noop rows."""
    r = np.random.default_rng(rows * 1000 + n_words)
    masks = r.integers(0, 2 ** 32, size=(rows, n_words), dtype=np.uint32)
    delta = r.integers(0, 2 ** 32, size=n_words, dtype=np.uint32)
    signs = r.integers(-1, 2, size=rows).astype(np.int32)
    got = np.asarray(ops.bitmap_patch(masks, delta, signs))
    twin = np.asarray(ref.bitmap_patch_ref(jnp.asarray(masks),
                                           jnp.asarray(delta),
                                           jnp.asarray(signs)))
    oracle = ref.bitmap_patch_np(masks, delta, signs)
    assert np.array_equal(got, oracle)
    assert np.array_equal(twin, oracle)
    # semantic spot checks: OR rows superset delta, AND-NOT rows disjoint
    assert np.all((got[signs > 0] & delta) == delta)
    assert not np.any(got[signs < 0] & delta)
    assert np.array_equal(got[signs == 0], masks[signs == 0])


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 12), st.integers(1, 3000), st.integers(0, 2 ** 32 - 1))
def test_bitmap_patch_roundtrip_property(rows, n_words, seed):
    """OR then AND-NOT of the same delta must clear every delta bit."""
    r = np.random.default_rng(seed)
    masks = r.integers(0, 2 ** 32, size=(rows, n_words), dtype=np.uint32)
    delta = r.integers(0, 2 ** 32, size=n_words, dtype=np.uint32)
    ones = np.ones(rows, dtype=np.int32)
    ored = np.asarray(ops.bitmap_patch(masks, delta, ones))
    cleared = np.asarray(ops.bitmap_patch(ored, delta, -ones))
    assert np.array_equal(cleared, masks & ~delta)


@pytest.mark.parametrize("b,h,kv,s,d,dtype", [
    (2, 8, 2, 1000, 64, np.float32),
    (1, 4, 4, 512, 128, np.float32),
    (3, 16, 8, 700, 32, np.float32),
    (2, 8, 8, 256, 64, np.float32),
    (2, 8, 2, 512, 64, jnp.bfloat16),
])
def test_flash_decode_sweep(b, h, kv, s, d, dtype):
    qv = jnp.asarray(RNG.normal(size=(b, h, d)), dtype=dtype)
    kc = jnp.asarray(RNG.normal(size=(b, kv, s, d)), dtype=dtype)
    vc = jnp.asarray(RNG.normal(size=(b, kv, s, d)), dtype=dtype)
    lens = RNG.integers(1, s + 1, size=b)
    lm = (np.arange(s)[None, :] < lens[:, None])
    o1 = ops.flash_decode(qv, kc, vc, lm)
    o2 = ref.flash_decode_ref(qv, kc, vc, jnp.asarray(lm))
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), rtol=tol, atol=tol)


# ------------------------------------------------- block-clamp regression
def test_align_block_n_unit():
    """The clamp must stay a multiple of 32 (the packed-word kernels assert
    it) while never exceeding the padded row range by more than one word.
    The old ``min(block_n, max(128, n))`` clamp handed 137 straight through."""
    assert ops._align_block_n(1024, 137) == 160      # round UP, not down
    assert ops._align_block_n(1024, 4096) == 1024    # large n: untouched
    assert ops._align_block_n(1024, 128) == 128
    assert ops._align_block_n(100, 5000) == 128      # floor wins, aligned
    assert ops._align_block_n(256, 1) == 128
    for n in (1, 31, 97, 137, 161, 4097):
        for bn in (100, 128, 256, 1024, 4096):
            got = ops._align_block_n(bn, n)
            assert got % 32 == 0 and got >= 32, (bn, n, got)


@pytest.mark.parametrize("n", [97, 137, 261])
def test_adversarial_row_counts_all_kernels(n):
    """Every tunable wrapper at odd row counts with an oversized requested
    block_n: the clamp path must produce aligned blocks and oracle-exact
    results (the regression that motivated ``_align_block_n``)."""
    d, m, k, nq = 32, 4, 7, 3
    Q = RNG.normal(size=(nq, d)).astype(np.float32)
    X = RNG.normal(size=(n, d)).astype(np.float32)
    mask = RNG.random(n) < 0.6
    pad = (-n) % 32
    dense = RNG.random((2, n)) < 0.6
    words = np.stack([
        np.packbits(np.pad(mk, (0, pad)), bitorder="little").view(np.uint32)
        for mk in dense])
    sid = RNG.integers(0, 2, size=nq).astype(np.int32)

    def check(got, want):
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(want[1]))

    check(ops.scoped_topk(Q, X, mask, k=k, block_n=4096),
          ref.scoped_topk_ref(jnp.asarray(Q), jnp.asarray(X),
                              jnp.asarray(mask), k=k))
    check(ops.multi_scope_topk(Q, X, words, sid, k=k, block_n=4096),
          ref.multi_scope_topk_ref(jnp.asarray(Q), jnp.asarray(X),
                                   jnp.asarray(words), jnp.asarray(sid),
                                   k=k))
    q_i8, q_s = _quantize(Q)
    x_i8, x_s = _quantize(X)
    sq = _q_norms(x_i8, x_s)
    check(ops.scoped_topk_i8(q_i8, q_s, x_i8, x_s, sq, mask, k=k,
                             block_n=4096),
          ref.scoped_topk_i8_ref(q_i8, q_s, x_i8, x_s, sq, mask, k=k))
    check(ops.multi_scope_topk_i8(q_i8, q_s, x_i8, x_s, sq, words, sid,
                                  k=k, block_n=4096),
          ref.multi_scope_topk_i8_ref(q_i8, q_s, x_i8, x_s, sq, words, sid,
                                      k=k))
    lut = RNG.normal(size=(nq, m, 256)).astype(np.float32)
    codes = RNG.integers(0, 256, size=(n, m)).astype(np.uint8)
    check(ops.scoped_topk_pq(lut, codes, mask, k=k, block_n=4096),
          ref.scoped_topk_pq_ref(lut, codes, mask, k=k))
    check(ops.multi_scope_topk_pq(lut, codes, words, sid, k=k,
                                  block_n=4096),
          ref.multi_scope_topk_pq_ref(lut, codes, words, sid, k=k))
