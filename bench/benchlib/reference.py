"""The plain reference: brute-force scoped top-k over string-prefix scopes.

A directory scope is what a user means by it: the entries whose directory
path is the anchor (non-recursive) or starts with it (recursive). The
reference keeps every directory's path as a string and rewrites the
strings under a MOVE or MERGE; it has no scope index, no cache and no
planner. On each tree state it sorts the directory paths, so a scope is a
range of ranks: a recursive scope at ``/a/b/`` is every path in
``["/a/b/", "/a/b0")`` (``'0'`` follows ``'/'``), a non-recursive one the
single rank of ``/a/b/``.

Scores are exact inner products at ``precision="highest"`` (full fp32 on
a TPU), over every row, computed on the device in blocks of queries.
Nothing here imports the program.
"""
from __future__ import annotations

import bisect
import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

K = 10
QUERY_BLOCK = 64


class ScopeState:
    """Directory paths and entry membership under a sequence of DSM ops.
    The paths are also kept in one sorted list, so the directories under a
    prefix are a range of it."""

    def __init__(self, dir_paths: Sequence[str], entry_dir: np.ndarray,
                 keep: Optional[np.ndarray] = None):
        """``keep``: a flag per directory; the others are left out (the
        directories that hold no entry in their subtree)."""
        self.paths: Dict[int, str] = {
            i: p for i, p in enumerate(dir_paths)
            if keep is None or keep[i]}
        self.node_of: Dict[str, int] = {p: i for i, p in self.paths.items()}
        self.entry_dir = np.asarray(entry_dir, np.int64).copy()
        self._sorted: List[str] = sorted(self.node_of)
        self._entry_rank: Optional[np.ndarray] = None

    def _under(self, prefix: str) -> Tuple[int, int]:
        s = self._sorted
        lo = bisect.bisect_left(s, prefix)
        return lo, bisect.bisect_left(s, prefix[:-1] + "0", lo)

    def _rekey(self, old: str, new: str) -> None:
        """Every path that starts with ``old`` starts with ``new`` instead."""
        lo, hi = self._under(old)
        moved = self._sorted[lo:hi]
        del self._sorted[lo:hi]
        ids = [self.node_of.pop(p) for p in moved]
        renamed = [new + p[len(old):] for p in moved]     # still sorted
        for i, q in zip(ids, renamed):
            self.paths[i] = q
            self.node_of[q] = i
        if len(renamed) > 16:
            self._sorted.extend(renamed)        # two sorted runs: one merge
            self._sorted.sort()
        else:
            for q in renamed:
                bisect.insort(self._sorted, q)
        self._entry_rank = None

    def apply(self, kind: str, src: str, dst: str) -> None:
        """MOVE ``src`` under ``dst``, or MERGE ``src`` into ``dst``."""
        if kind == "move":
            name = src.rstrip("/").rsplit("/", 1)[-1]
            self._rekey(src, dst + name + "/")
        elif kind == "merge":
            s, d = self.node_of.pop(src), self.node_of[dst]
            self.entry_dir[self.entry_dir == s] = d
            del self.paths[s]
            del self._sorted[bisect.bisect_left(self._sorted, src)]
            self._rekey(src, dst)
        else:
            raise ValueError(f"unknown op {kind!r}")

    def _index(self) -> None:
        if self._entry_rank is not None:
            return
        rank = np.full(max(self.paths) + 1, -1, np.int64)
        nodes = np.fromiter(map(self.node_of.__getitem__, self._sorted),
                            np.int64, len(self._sorted))
        rank[nodes] = np.arange(len(nodes))
        self._entry_rank = rank[self.entry_dir].astype(np.int32)

    def dirs(self) -> List[str]:
        return list(self._sorted)

    def entry_rank(self) -> np.ndarray:
        """(N,) rank of each entry's directory among the sorted paths."""
        self._index()
        return self._entry_rank

    def scope(self, anchor: str, recursive: bool) -> Tuple[int, int]:
        """``[lo, hi)`` ranks of the directories in the scope."""
        if recursive:
            return self._under(anchor)
        s = self._sorted
        lo = bisect.bisect_left(s, anchor)
        return lo, lo + int(lo < len(s) and s[lo] == anchor)


def scope_bytes(sorted_rank: np.ndarray,
                ranges: Sequence[Tuple[int, int]], dim: int,
                n_queries: int) -> int:
    """Least bytes one batch must read: every row in the union of its
    requests' scopes once (``dim`` fp32 each), plus its fp32 queries.
    ``sorted_rank`` is :meth:`ScopeState.entry_rank`, sorted."""
    rows = 0
    end = -1
    for lo, hi in sorted(ranges):
        lo = max(lo, end)
        if hi > lo:
            rows += int(np.searchsorted(sorted_rank, hi)
                        - np.searchsorted(sorted_rank, lo))
            end = hi
    return 4 * dim * (rows + n_queries)


@functools.lru_cache(maxsize=None)
def _block_fn(precision: str):
    import jax
    import jax.numpy as jnp
    prec = {"highest": jax.lax.Precision.HIGHEST,
            "high": jax.lax.Precision.HIGH}[precision]

    @jax.jit
    def block(x, q, rank, lo, hi, ids):
        """Reference top-k of each query over its scope, the scope's size,
        and the exact score and membership of each id in ``ids``."""
        s = jnp.matmul(q, x.T, precision=prec)                  # (B, N)
        inside = (rank[None, :] >= lo[:, None]) & (rank[None, :] < hi[:, None])
        vals, idx = jax.lax.top_k(jnp.where(inside, s, -jnp.inf), K)
        safe = jnp.maximum(ids, 0)
        rows = x[safe]                                          # (B, K, d)
        got = jnp.einsum("bkd,bd->bk", rows, q, precision=prec)
        r = rank[safe]
        member = (ids >= 0) & (r >= lo[:, None]) & (r < hi[:, None])
        return vals, idx, inside.sum(axis=1), got, member
    return block


@dataclass
class Answers:
    """The reference's view of a set of answered queries."""
    top: np.ndarray             # (Q, K) best scores, -inf padded
    top_ids: np.ndarray         # (Q, K)
    size: np.ndarray            # (Q,) scope size
    got: np.ndarray             # (Q, K) exact score of each returned id
    member: np.ndarray          # (Q, K) returned id lies in the scope


def answer(x_dev, queries: np.ndarray, entry_rank: np.ndarray,
           ranges: np.ndarray, ids: np.ndarray,
           precision: str = "highest") -> Answers:
    """Reference answers for ``queries`` (Q, d) on one tree state, with
    ``ranges`` (Q, 2) from :meth:`ScopeState.scope` and the ids (Q, K) a
    system returned (-1 padded)."""
    import jax.numpy as jnp
    fn = _block_fn(precision)
    rank = jnp.asarray(entry_rank)
    out = []
    for lo in range(0, len(queries), QUERY_BLOCK):
        n = min(QUERY_BLOCK, len(queries) - lo)
        pad = lambda a, v: np.concatenate(          # noqa: E731
            [a[lo: lo + n], np.full((QUERY_BLOCK - n,) + a.shape[1:], v,
                                    a.dtype)])
        res = fn(x_dev, jnp.asarray(pad(queries.astype(np.float32), 0)), rank,
                 jnp.asarray(pad(ranges[:, 0].astype(np.int32), 0)),
                 jnp.asarray(pad(ranges[:, 1].astype(np.int32), 0)),
                 jnp.asarray(pad(ids.astype(np.int32), -1)))
        out.append([np.asarray(a)[:n] for a in res])
    cat = [np.concatenate([o[j] for o in out]) for j in range(5)]
    return Answers(top=cat[0], top_ids=cat[1].astype(np.int64),
                   size=cat[2].astype(np.int64), got=cat[3], member=cat[4])


@dataclass
class Verdict:
    wrong_answers: int          # count, size or scope membership wrong
    score_err: float            # widest |returned score - exact score|
    rank_gap: float             # widest shortfall of a returned rank
    checked: int

    def numbers(self) -> Dict[str, float]:
        """The compared numbers: wrong answers (exact), and the widest gap
        of either kind (``score_gap``)."""
        return {"wrong_answers": self.wrong_answers,
                "score_gap": max(self.score_err, self.rank_gap)}


def judge(ids: np.ndarray, scores: np.ndarray, ref: Answers) -> Verdict:
    """Compare returned (ids, scores) (Q, K) with the reference.

    An answer is wrong when it returns another number of results than
    ``min(K, scope size)``, a padded slot before a filled one, an id twice,
    or an id outside the scope. Of the rest, ``score_err`` is the widest
    gap between a returned score and the exact score of that id, and
    ``rank_gap`` the widest amount by which the i-th best exact score among
    the returned ids falls below the scope's i-th best (0 for the exact
    top-k, whatever the order of ties)."""
    wrong = 0
    score_err = 0.0
    rank_gap = 0.0
    for i in range(len(ids)):
        want = int(min(K, ref.size[i]))
        valid = ids[i] >= 0
        n = int(valid.sum())
        if (n != want or not valid[:n].all()
                or len(set(ids[i][:n].tolist())) != n
                or not ref.member[i][:n].all()
                or not np.isfinite(scores[i][:n]).all()):
            wrong += 1
            continue
        if n == 0:
            continue
        got = ref.got[i][:n].astype(np.float64)
        score_err = max(score_err, float(np.max(np.abs(
            scores[i][:n].astype(np.float64) - got))))
        rank_gap = max(rank_gap, float(np.max(
            ref.top[i][:n].astype(np.float64) - np.sort(got)[::-1])))
    return Verdict(wrong, score_err, rank_gap, len(ids))
