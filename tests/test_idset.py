"""RoaringBitmap: property tests against Python sets (the obvious oracle)."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.idset import ARRAY_MAX, RoaringBitmap

ids = st.lists(st.integers(0, 1 << 20), max_size=300)


@settings(max_examples=50, deadline=None)
@given(ids, ids)
def test_set_algebra_matches_python_sets(a, b):
    ra, rb = RoaringBitmap(a), RoaringBitmap(b)
    sa, sb = set(a), set(b)
    assert set(ra.to_array().tolist()) == sa
    assert set((ra | rb).to_array().tolist()) == sa | sb
    assert set((ra & rb).to_array().tolist()) == sa & sb
    assert set((ra - rb).to_array().tolist()) == sa - sb
    assert len(ra) == len(sa)
    for x in list(sa)[:10]:
        assert x in ra


@settings(max_examples=30, deadline=None)
@given(ids, ids)
def test_inplace_ops(a, b):
    ra, rb = RoaringBitmap(a), RoaringBitmap(b)
    sa, sb = set(a), set(b)
    ra |= rb
    assert set(ra.to_array().tolist()) == sa | sb
    ra -= rb
    assert set(ra.to_array().tolist()) == (sa | sb) - sb


@settings(max_examples=30, deadline=None)
@given(ids, st.lists(st.integers(0, 1 << 20), max_size=50))
def test_remove(a, rm):
    ra = RoaringBitmap(a)
    ra.remove_many(np.asarray(rm, np.uint32))
    assert set(ra.to_array().tolist()) == set(a) - set(rm)


def test_container_promotion_and_demotion():
    # force a dense container (> ARRAY_MAX within one 64k chunk)
    ids = np.arange(ARRAY_MAX + 100, dtype=np.uint32)
    r = RoaringBitmap.from_array(ids)
    assert r.stats()["bitmap_containers"] == 1
    # difference that drops it back below the threshold
    r -= RoaringBitmap.from_array(ids[: ARRAY_MAX])
    assert len(r) == 100
    assert set(r.to_array().tolist()) == set(range(ARRAY_MAX, ARRAY_MAX + 100))


def test_dense_mask_and_words():
    ids = [0, 5, 31, 32, 63, 1000]
    r = RoaringBitmap(ids)
    mask = r.to_bool_mask(1024)
    assert sorted(np.nonzero(mask)[0].tolist()) == sorted(set(ids))
    words = r.to_words(1024)
    assert words.dtype == np.uint32
    unpacked = np.unpackbits(words.view(np.uint8), bitorder="little")
    assert sorted(np.nonzero(unpacked)[0].tolist()) == sorted(set(ids))


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 1 << 18), max_size=300),
       st.integers(0, (1 << 18) + 40))
def test_to_words_fast_path_matches_packbits(a, n):
    """The container-direct word emitter must be bit-identical to the
    dense-mask + packbits roundtrip it replaced, for any export length."""
    r = RoaringBitmap(a)
    padded = ((n + 31) // 32) * 32
    mask = np.zeros(padded, dtype=bool)
    keep = np.asarray([x for x in set(a) if x < padded], dtype=np.int64)
    mask[keep] = True
    want = np.packbits(mask, bitorder="little").view(np.uint32)
    assert np.array_equal(r.to_words(n), want)
    bmask = r.to_bool_mask(n)
    assert bmask.dtype == bool and bmask.shape == (n,)
    assert np.array_equal(bmask, mask[:n])


def test_to_words_dense_container_fast_path():
    """A bitmap container (> ARRAY_MAX dense ids) is emitted by direct word
    copy; spot-check both container kinds in one set."""
    dense = np.arange(ARRAY_MAX + 200, dtype=np.uint32)        # bitmap
    sparse = np.asarray([70000, 70003, 200000], np.uint32)     # arrays
    r = RoaringBitmap(np.concatenate([dense, sparse]))
    n = 200001
    words = r.to_words(n)
    got = np.nonzero(np.unpackbits(words.view(np.uint8),
                                   bitorder="little")[:n])[0]
    assert np.array_equal(got, np.sort(np.concatenate([dense, sparse])))


def test_union_many_and_copy_isolation():
    parts = [RoaringBitmap(range(i, i + 10)) for i in range(0, 100, 10)]
    u = RoaringBitmap.union_many(parts)
    assert len(u) == 100
    c = u.copy()
    c.remove(0)
    assert 0 in u and 0 not in c


def test_equality_and_empty():
    assert RoaringBitmap([1, 2]) == RoaringBitmap([2, 1])
    assert not RoaringBitmap()
    assert len(RoaringBitmap()) == 0
    assert RoaringBitmap().to_array().shape == (0,)
