"""Top-k result comparison under fp32 rounding.

Two exact executors that do not run one program (flat vs sharded, device vs
a NumPy reference) rank the same rows with the same fp32 arithmetic but not
the same matmul shape, and a backend may round a dot product differently per
shape. So
"the same answer" means: the same number of results per query, scores equal
position by position within a stated fp32 tolerance, and the same ids — except
that an id may be swapped for another whose score ties it within that
tolerance at the k boundary (and tied neighbours may trade places).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

# a few fp32 ULP, relative to the largest score magnitude in the row
ULP_RTOL = 4 * float(np.finfo(np.float32).eps)


def topk_mismatch(got_scores, got_ids, want_scores, want_ids,
                  rtol: float = ULP_RTOL, atol: float = 0.0
                  ) -> Optional[str]:
    """``None`` when the two (q, k) results agree under the contract above,
    else a description of the first disagreement. ``-1`` ids are empty
    slots. Two scores ``a``, ``b`` of a row tie when ``|a - b| <= atol +
    rtol * max(1, max |score| of the row)``."""
    got_scores = np.atleast_2d(np.asarray(got_scores, np.float64))
    want_scores = np.atleast_2d(np.asarray(want_scores, np.float64))
    got_ids = np.atleast_2d(np.asarray(got_ids, np.int64))
    want_ids = np.atleast_2d(np.asarray(want_ids, np.int64))
    if got_ids.shape[0] != want_ids.shape[0]:
        return f"{got_ids.shape[0]} result rows, want {want_ids.shape[0]}"
    for r in range(want_ids.shape[0]):
        gv, wv = got_ids[r] >= 0, want_ids[r] >= 0
        gs, ws = got_scores[r][gv], want_scores[r][wv]
        if len(gs) != len(ws):
            return f"row {r}: {len(gs)} results, want {len(ws)}"
        if not len(ws):
            continue
        tol = atol + rtol * max(1.0, float(np.max(np.abs(ws))))
        if np.any(np.abs(gs - ws) > tol):
            return (f"row {r}: scores {gs.tolist()} vs {ws.tolist()} "
                    f"(tolerance {tol:.3g})")
        want_of = dict(zip(want_ids[r][wv].tolist(), ws.tolist()))
        for miss in set(want_of) - set(got_ids[r][gv].tolist()):
            if want_of[miss] - ws.min() > tol:
                return (f"row {r}: id {miss} (score {want_of[miss]}) "
                        f"missing above the k-th score {ws.min()}")
    return None
