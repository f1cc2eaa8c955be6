"""The least bytes a batch must read, from which ``scan_roofline`` is
taken."""
import benchpath  # noqa: F401

import numpy as np

from benchlib import reference
from benchlib.reference import ScopeState


def test_scope_bytes_counts_the_union_of_scopes_once():
    # dirs: / (rank 0), /a/ (1), /a/b/ (2), /c/ (3)
    state = ScopeState(["/", "/a/", "/a/b/", "/c/"], np.array(
        [1, 1, 2, 2, 2, 3, 0]))
    rank = np.sort(state.entry_rank())
    a = state.scope("/a/", True)           # 5 rows
    ab = state.scope("/a/b/", True)        # 3 rows, inside /a/
    c = state.scope("/c/", False)          # 1 row
    dim = 8
    assert reference.scope_bytes(rank, [a], dim, 1) == 4 * dim * (5 + 1)
    assert reference.scope_bytes(rank, [a, ab, ab], dim, 3) == 4 * dim * (5 + 3)
    assert reference.scope_bytes(rank, [ab, c], dim, 2) == 4 * dim * (4 + 2)
    root = state.scope("/", True)
    assert reference.scope_bytes(rank, [c, root, a], dim, 3) == 4 * dim * (7 + 3)
    assert reference.scope_bytes(rank, [state.scope("/zz/", True)], dim,
                                 1) == 4 * dim * 1
