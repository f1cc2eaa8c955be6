"""Cross-validation of the three scope-resolution strategies (§III–IV).

The defining property of the design space: PE-ONLINE, PE-OFFLINE and TRIEHI
are *interchangeable implementations of the same semantics*. A random op
sequence (insert/delete/mkdir/move/merge + resolve) must keep all three in
exact agreement, and every structural invariant (ancestor materialization,
the TrieHI Eq. 1 aggregate) must hold afterwards.
"""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import STRATEGIES, make_scope_index
from repro.core import paths as P

SEGS = ["a", "b", "c", "d"]

path_st = st.lists(st.sampled_from(SEGS), min_size=0, max_size=4).map(tuple)


class Op:
    def __init__(self, kind, **kw):
        self.kind = kind
        self.kw = kw

    def __repr__(self):
        return f"Op({self.kind}, {self.kw})"


ops_st = st.lists(
    st.one_of(
        st.tuples(st.just("insert"), st.integers(0, 99), path_st),
        st.tuples(st.just("delete"), st.integers(0, 99)),
        st.tuples(st.just("mkdir"), path_st),
        st.tuples(st.just("move"), path_st, path_st),
        st.tuples(st.just("merge"), path_st, path_st),
        st.tuples(st.just("remove"), path_st),
    ),
    max_size=30)


def apply_all(indexes, op):
    """Apply op to every index; all must agree on success/failure (and for
    remove, on the removed entry-id set)."""
    results = []
    removed_sets = []
    for idx in indexes:
        try:
            kind = op[0]
            if kind == "insert":
                idx.insert(op[1], op[2])
            elif kind == "delete":
                idx.delete(op[1])
            elif kind == "mkdir":
                idx.mkdir(op[1])
            elif kind == "move":
                idx.move(op[1], op[2])
            elif kind == "merge":
                idx.merge(op[1], op[2])
            elif kind == "remove":
                removed_sets.append(set(idx.remove(op[1]).to_array().tolist()))
            results.append("ok")
        except (KeyError, ValueError) as e:
            results.append(type(e).__name__)
    assert len(set(results)) == 1, (op, results, "strategies disagree")
    assert len(set(map(frozenset, removed_sets))) <= 1, (op, removed_sets)


@settings(max_examples=60, deadline=None)
@given(ops_st, st.lists(path_st, max_size=6))
def test_strategies_agree_under_random_ops(ops, probe_paths):
    indexes = [make_scope_index(n) for n in STRATEGIES]
    inserted = {}
    for op in ops:
        if op[0] == "insert" and op[1] in inserted:
            continue  # re-inserting an id is app-level misuse; skip
        if op[0] == "delete" and op[1] not in inserted:
            continue
        apply_all(indexes, op)
        if op[0] == "insert":
            inserted[op[1]] = op[2]
        elif op[0] == "delete":
            inserted.pop(op[1], None)
        elif op[0] == "remove":
            # entries under the removed subtree are unbound everywhere
            inserted = {eid: p for eid, p in inserted.items()
                        if indexes[0].entry_dir(eid) is not None}
    for idx in indexes:
        idx.check_invariants()
    # all resolutions agree on every probe path, recursive + non-recursive
    for path in list(probe_paths) + [()]:
        for recursive in (True, False):
            sets = [set(idx.resolve(path, recursive=recursive)
                        .to_array().tolist()) for idx in indexes]
            assert sets[0] == sets[1] == sets[2], (path, recursive, sets)
    # catalog agreement: every entry reports the same current directory
    dirs = [{eid: idx.entry_dir(eid) for eid in inserted} for idx in indexes]
    assert dirs[0] == dirs[1] == dirs[2]


@pytest.mark.parametrize("name", list(STRATEGIES))
def test_paper_running_example(name):
    """Figure 2/3/4/5 walk-through: /HR, /Dept_A, /Dept_B, /Archive."""
    idx = make_scope_index(name)
    idx.insert(1, "/HR/")
    idx.insert(2, "/HR/Policies/")
    idx.insert(5, "/Dept_A/")
    idx.insert(8, "/Dept_A/OKR/")
    idx.insert(9, "/Dept_B/OKR/")
    idx.insert(7, "/Archive/HR/")
    # DSQ
    assert set(idx.resolve("/HR/", True)) == {1, 2}
    assert set(idx.resolve("/HR/", False)) == {1}
    assert set(idx.resolve("/Dept_A/", True)) == {5, 8}
    assert set(idx.resolve("/Archive/", True)) == {7}
    assert set(idx.resolve("/nonexistent/", True)) == set()
    # MOVE /Dept_A/ under /Dept_B/
    idx.move("/Dept_A/", "/Dept_B/")
    assert set(idx.resolve("/Dept_B/", True)) == {5, 8, 9}
    assert not idx.has_dir("/Dept_A/")
    assert idx.entry_dir(8) == ("Dept_B", "Dept_A", "OKR")
    # move back, then MERGE with OKR conflict: doc_8 + doc_9 unioned
    idx.move("/Dept_B/Dept_A/", "/")
    idx.merge("/Dept_A/", "/Dept_B/")
    assert set(idx.resolve("/Dept_B/OKR/", True)) == {8, 9}
    assert set(idx.resolve("/Dept_B/", False)) == {5}
    idx.check_invariants()


@pytest.mark.parametrize("name", list(STRATEGIES))
def test_move_rejects_cycle_and_conflict(name):
    idx = make_scope_index(name)
    idx.insert(1, "/a/b/")
    idx.insert(2, "/c/")
    with pytest.raises(ValueError):
        idx.move("/a/", "/a/b/")          # into own subtree
    idx.mkdir("/c/a/")
    with pytest.raises(ValueError):
        idx.move("/a/", "/c/")            # name conflict -> use merge
    idx.check_invariants()


@pytest.mark.parametrize("name", list(STRATEGIES))
def test_exclusion_query(name):
    idx = make_scope_index(name)
    idx.insert(1, "/docs/v2/")
    idx.insert(2, "/archive/v1/")
    idx.insert(3, "/docs/")
    got = idx.resolve_exclusion("/", ["/archive/"], recursive=True)
    assert set(got) == {1, 3}


@pytest.mark.parametrize("name", list(STRATEGIES))
def test_deep_chain_costs_shape(name):
    """Sanity on the cost *shape*: resolving deep anchors touches the
    expected number of keys (m_q for PE-ONLINE, O(t) for TrieHI)."""
    from repro.core.interface import ResolveStats
    idx = make_scope_index(name)
    depth = 12
    for d in range(depth):
        idx.insert(d, "/" + "/".join(f"s{i}" for i in range(d + 1)) + "/")
    stats = ResolveStats()
    got = idx.resolve("/s0/", recursive=True, stats=stats)
    assert set(got) == set(range(depth))
    if name == "pe_online":
        assert stats.subpath_keys == depth      # enumerated whole subtree
    if name == "triehi":
        assert stats.node_visits == 2           # root + s0
    if name == "pe_offline":
        assert stats.posting_fetches == 1       # one materialized lookup


@pytest.mark.parametrize("name", list(STRATEGIES))
def test_wildcard_pattern_dsq(name):
    """Beyond-paper: §IV-A derived path patterns (the paper's named future
    work). All strategies agree; TrieHI answers by branch-pruned traversal."""
    idx = make_scope_index(name)
    idx.insert(1, "/users/u0/sessions/s0/")
    idx.insert(2, "/users/u1/sessions/s0/")
    idx.insert(3, "/users/u1/sessions/s1/")
    idx.insert(4, "/other/u9/sessions/s0/")
    idx.insert(5, "/users/u1/sessions/s1/deep/")
    assert set(idx.resolve_pattern("/users/*/sessions/s0/")) == {1, 2}
    assert set(idx.resolve_pattern("/users/u1/*/")) == {2, 3, 5}
    assert set(idx.resolve_pattern("/users/u1/sessions/*/",
                                   recursive=False)) == {2, 3}
    assert set(idx.resolve_pattern("/nope/*/")) == set()


@settings(max_examples=25, deadline=None)
@given(ops_st, st.lists(st.sampled_from(SEGS + ["*"]),
                        min_size=1, max_size=3).map(tuple))
def test_wildcard_strategies_agree(ops, pattern):
    indexes = [make_scope_index(n) for n in STRATEGIES]
    inserted = set()
    for op in ops:
        if op[0] == "insert" and op[1] in inserted:
            continue
        if op[0] == "delete" and op[1] not in inserted:
            continue
        apply_all(indexes, op)
        if op[0] == "insert":
            inserted.add(op[1])
        elif op[0] == "delete":
            inserted.discard(op[1])
    sets = [set(idx.resolve_pattern(pattern).to_array().tolist())
            for idx in indexes]
    assert sets[0] == sets[1] == sets[2], (pattern, sets)
