"""The DSM stream is valid by construction against the live tree: at every
step both ends exist and neither holds the other, the reference's scopes
follow it, and the program's TrieHI applies every op and ends with the
same directories."""
import benchpath  # noqa: F401

import numpy as np
import pytest

from benchlib import reference, twin
from benchlib.cell import load_loop

CFG = {"structure_seed": 7, "entries": 4000, "dim": 16,
       "namespaces": [{"name": "fs", "dirs": 800, "avg_depth": 11.95,
                       "depth_sd": 4.0, "entry_zipf": 1.3,
                       "every_dir_occupied": True, "prefix": "w"}],
       "query_namespace": "fs",
       "vectors": {"branch_noise": 0.35, "query_noise": 0.3}}
TRAFFIC = {"loop": "closed", "clients": 16, "pool": 600, "dsm_share": 0.25,
           "shallow_depth": 3, "anchor_zipf": 1.0, "recursive_share": 0.8}
CLOSED = load_loop("closed")


def _stream(seed, n=600):
    corpus = twin.build_corpus(CFG)
    st = CLOSED.build_stream(CFG, TRAFFIC, corpus, seed)
    ops, is_dsm, qs = CLOSED.timeline(st, n)
    return corpus, st, ops, qs


def _initial_state(ns):
    return reference.ScopeState(ns.tree.paths(), ns.entry_dir,
                                twin.live_nodes(ns.tree, ns.entry_dir))


@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_every_op_is_valid_on_the_reference_tree(seed):
    corpus, st, ops, qs = _stream(seed)
    assert len(ops) == 600 // 3          # one op after every 3 queries
    assert {k for k, _, _ in ops} == {"move", "merge"}
    state = _initial_state(corpus.primary)
    for kind, src, dst in ops:
        dirs = set(state.paths.values())
        assert src in dirs and dst in dirs and src != "/"
        assert not src.startswith(dst) and not dst.startswith(src)
        state.apply(kind, src, dst)
        assert len(set(state.paths.values())) == len(state.paths)
    # every entry's directory is a live one
    assert set(np.unique(state.entry_dir)) <= set(state.paths)


def test_same_seed_same_stream_and_seeds_reorder_fixed_pools():
    a, b = _stream(5), _stream(5)
    assert a[2] == b[2] and a[3] == b[3]
    c = _stream(6)
    assert c[2] == a[2]                 # the deployment's op sequence
    ta, tc = a[1].templates, c[1].templates
    assert not np.array_equal(ta.entry, tc.entry)
    # the template pool is the deployment's; a seed only reorders it
    key = lambda t: sorted(zip(t.entry.tolist(), t.level.tolist(),   # noqa
                               t.recursive.tolist()))
    assert key(ta) == key(tc)


def test_program_applies_the_stream_and_agrees_on_scopes():
    from repro.vectordb import DirectoryVectorDB
    corpus, st, ops, qs = _stream(3)
    ns = corpus.primary
    db = DirectoryVectorDB(dim=4, scope_strategy="triehi")
    db.ingest(np.zeros((corpus.n_entries, 4), np.float32), ns.entry_paths())
    state = _initial_state(ns)
    idx = db.namespaces["fs"]
    for j in range(0, len(ops), 7):
        group = ops[j: j + 7]
        res = db.dsm_batch(group)
        assert all(e is None for e in res.errors), res.errors
        for op in group:
            state.apply(*op)
        rank = state.entry_rank()
        for anchor, rec in qs[j: j + 20]:
            lo, hi = state.scope(anchor, rec)
            want = np.flatnonzero((rank >= lo) & (rank < hi))
            got = np.sort(idx.resolve(anchor, recursive=rec).to_array())
            np.testing.assert_array_equal(got, want)
    prog = {"/" + "".join(s + "/" for s in p) if p else "/"
            for p in idx.list_dirs()}
    assert prog == set(state.dirs())


def test_every_directory_is_occupied_when_the_config_says_so():
    corpus = twin.build_corpus(CFG)
    ns = corpus.primary
    assert len(ns.entry_dir) == CFG["entries"]
    assert set(np.unique(ns.entry_dir)) == set(range(1, len(ns.tree)))
    assert twin.live_nodes(ns.tree, ns.entry_dir).all()


class _Slot:
    """Counts the ops a generator makes due."""

    def __init__(self):
        self.due = []

    def make_due(self, t):
        self.due.append(t)


@pytest.mark.parametrize("share,every", [(0.1, 9), (0.25, 3), (0.0, 0)])
def test_dsm_share_of_operations(share, every):
    corpus = twin.build_corpus(CFG)
    st = CLOSED.build_stream(CFG, dict(TRAFFIC, dsm_share=share), corpus, 1)
    assert st.every == every
    due = [n for n in range(900) if st.op_due_after(n)]
    assert len(due) == (900 // every if every else 0)
    assert len(st.dsm) == (-(-600 // every) if every else 0)
    ops, flags, qs = CLOSED.timeline(st, 600)
    if every:
        assert flags.sum() / len(flags) == pytest.approx(share, abs=0.01)


def test_a_pool_no_larger_than_the_clients_keeps_its_order():
    corpus = twin.build_corpus(CFG)
    small = dict(TRAFFIC, pool=16, clients=16)
    a = CLOSED.build_stream(CFG, small, corpus, 1).templates
    b = CLOSED.build_stream(CFG, small, corpus, 2).templates
    np.testing.assert_array_equal(a.entry, b.entry)
    np.testing.assert_array_equal(a.level, b.level)
