"""The control: the plain reference put in the program's place, one
precision step down.

The configurations state exact fp32 scores (``precision="highest"``); the
step below is ``"high"``: bfloat16 with three passes. It is written out
here (each operand split into a bfloat16 head and a bfloat16 tail, the
tail-by-tail product dropped, fp32 accumulation), so the control computes
the same numbers on every backend, the CPU included, where ``"high"`` is
exact fp32. Its answers are judged by
:func:`reference.judge` exactly as the program's are; the comparison must
call them wrong.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import numpy as np

from . import reference, twin

K = reference.K


@functools.lru_cache(maxsize=None)
def _block_fn():
    import jax
    import jax.numpy as jnp

    def split(a):
        # round to bfloat16 inside fp32 first: a convert pair would be
        # folded away by XLA's excess-precision rewrite, leaving one pass
        hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)

    def dot3(a, b):                                  # (B, d) x (N, d)
        ah, al = split(a)
        bh, bl = split(b)
        mm = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
        return mm(ah, bh.T) + (mm(ah, bl.T) + mm(al, bh.T))

    @jax.jit
    def block(x, q, rank, lo, hi):
        s = dot3(q, x)
        inside = (rank[None, :] >= lo[:, None]) & (rank[None, :] < hi[:, None])
        vals, idx = jax.lax.top_k(jnp.where(inside, s, -jnp.inf), K)
        return vals, jnp.where(jnp.isfinite(vals), idx, -1)
    return block


def answers(x_dev, queries: np.ndarray, entry_rank: np.ndarray,
            ranges: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(ids, scores) (Q, K) of the control, -1 / -inf padded."""
    import jax.numpy as jnp
    fn = _block_fn()
    rank = jnp.asarray(entry_rank)
    ids, scores = [], []
    B = reference.QUERY_BLOCK
    for lo in range(0, len(queries), B):
        n = min(B, len(queries) - lo)
        pad = lambda a: np.concatenate(               # noqa: E731
            [a[lo: lo + n], np.zeros((B - n,) + a.shape[1:], a.dtype)])
        v, i = fn(x_dev, jnp.asarray(pad(queries.astype(np.float32))), rank,
                  jnp.asarray(pad(ranges[:, 0].astype(np.int32))),
                  jnp.asarray(pad(ranges[:, 1].astype(np.int32))))
        scores.append(np.asarray(v)[:n])
        ids.append(np.asarray(i)[:n].astype(np.int64))
    return np.concatenate(ids), np.concatenate(scores)


def states_in_order(ns: twin.Namespace, ops: Sequence[Tuple[str, str, str]],
                    is_dsm: np.ndarray, queries: Sequence[Tuple[str, bool]]
                    ) -> List[Tuple[reference.ScopeState, List[int]]]:
    """Walk the stream's timeline in order; yields, per tree state, the
    queries issued on it (indices into ``queries``). The state object is
    reused: consume each before advancing."""
    state = reference.ScopeState(ns.tree.paths(), ns.entry_dir,
                                 twin.live_nodes(ns.tree, ns.entry_dir))
    qi = oi = 0
    batch: List[int] = []
    for d in is_dsm:
        if d:
            if batch:
                yield state, batch
                batch = []
            state.apply(*ops[oi])
            oi += 1
        else:
            batch.append(qi)
            qi += 1
    if batch:
        yield state, batch


def judge_control(corpus: twin.Corpus, vectors: np.ndarray,
                  qvecs: np.ndarray, ops, is_dsm, queries
                  ) -> reference.Verdict:
    """The control's verdict over every query of a stream, each on the
    tree as the DSM ops generated before it left it."""
    import jax.numpy as jnp
    x = jnp.asarray(vectors)
    wrong, err, gap, n = 0, 0.0, 0.0, 0
    for state, idx in states_in_order(corpus.primary, ops, is_dsm, queries):
        ranges = np.asarray([state.scope(*queries[i]) for i in idx],
                            np.int64)
        rank = state.entry_rank()
        q = qvecs[idx]
        ids, scores = answers(x, q, rank, ranges)
        ref = reference.answer(x, q, rank, ranges, ids)
        v = reference.judge(ids, scores, ref)
        wrong += v.wrong_answers
        err = max(err, v.score_err)
        gap = max(gap, v.rank_gap)
        n += v.checked
    return reference.Verdict(wrong, err, gap, n)
