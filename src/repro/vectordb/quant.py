"""Symmetric int8 scalar quantization — the device tier's compact row format.

The quantized tier trades exactness for bytes exactly the way production
VDBMSs ship it (SQ-8 in the Pan et al. / Ma et al. survey taxonomies): each
row is stored as int8 codes plus ONE fp32 scale, so the device store shrinks
~4x (``dim + 4`` bytes per row vs ``4 * dim``) and the scan reads a quarter
of the HBM bytes. Scoring is *asymmetric-free*: queries are quantized with
their own per-row scale, the MXU/ALU accumulates the int8 dot in int32, and
the two scales multiply back in at merge time:

    score(q, x)  ≈  dot_i32(q_i8, x_i8) * q_scale * x_scale

which is EXACT for the quantized operands (int32 accumulation never rounds
for d * 127^2 << 2^31), so the only error is the per-component rounding of
the codes themselves. The two-phase execution plan (int8 scan selects
``rescore_k >= k`` candidates, exact fp32 gather-rescore ranks the final
top-k) then erases that error for every candidate the scan surfaces — the
recall contract of ``benchmarks/bench_quantized.py``.

Convention: all-zero rows quantize to scale 1.0 / all-zero codes so
dequantization is total (no divide-by-zero, no NaN scores).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# int8 scan phase keeps this many candidates per query (times k) before the
# exact fp32 rescore, unless the caller passes an explicit ``rescore_k``
DEFAULT_RESCORE_FACTOR = 4

Q_MAX = 127


def quantize_rows(rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Per-row symmetric int8 quantization.

    Returns ``(codes (n, d) int8, scales (n,) float32)`` with
    ``scale = max|row| / 127`` (1.0 for all-zero rows) and
    ``codes = round(row / scale)`` clipped to ``[-127, 127]``.
    """
    rows = np.atleast_2d(np.asarray(rows, dtype=np.float32))
    amax = np.max(np.abs(rows), axis=1)
    scales = np.where(amax > 0.0, amax / Q_MAX, 1.0).astype(np.float32)
    codes = np.clip(np.rint(rows / scales[:, None]), -Q_MAX, Q_MAX)
    return codes.astype(np.int8), scales


def dequantize_rows(codes: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Inverse of :func:`quantize_rows`: ``codes * scale`` per row, fp32."""
    return codes.astype(np.float32) * np.asarray(
        scales, dtype=np.float32)[:, None]


def pq_rescore_factor(m: int) -> int:
    """Default PQ rescore window per k: ``DEFAULT_RESCORE_FACTOR`` up to 16
    subspaces, then growing with sqrt(M). A row's ADC score sums M
    independent subspace quantization errors, so its error grows like
    sqrt(M) while the gap between a scope's k-th and (k+1)-th exact scores
    does not; at the default dsub = 4 that is 4 below 64-d, 16 at 1024-d
    (M = 256, where a 4k window reached recall@10 0.891 on a near-isotropic
    WIKI-Dir twin and 16k reached 0.992 on a TPU v5e)."""
    return DEFAULT_RESCORE_FACTOR * max(1, math.ceil(math.sqrt(m / 16)))


def resolve_rescore_k(k: int, rescore_k: Optional[int], n: int) -> int:
    """Effective int8-phase candidate count: the caller's ``rescore_k``
    (defaulting to ``DEFAULT_RESCORE_FACTOR * k``), at least ``k``, at most
    the ``n`` rows that exist."""
    r = DEFAULT_RESCORE_FACTOR * k if rescore_k is None else int(rescore_k)
    return max(1, min(max(r, k), n)) if n > 0 else max(k, 1)


# -------------------------------------------------------------------- PQ/ADC
#
# Product quantization: split each row into M contiguous subvectors of
# dsub = dim / M components, k-means each subspace into 256 centroids, store
# one uint8 centroid index per subspace. A row costs M bytes instead of
# 4 * dim — 1/16 at the default dsub = 4 — which is what finally lets the
# device tier hold a corpus whose fp32 rows exceed the device byte budget.
#
# Scoring is asymmetric distance computation (ADC): the query is NOT
# quantized. Per query we build one (M, 256) lookup table of subvector
# scores against every centroid, and a row's approximate score is the sum
# of M table entries selected by its codes. The LUT folds the metric in so
# the scan itself is metric-free:
#
#   ip / cos :  lut[m, c] = q_m . C[m, c]          => sum = q . x_hat
#   l2       :  lut[m, c] = 2 q_m . C[m, c] - |C[m, c]|^2
#                                           => sum = 2 q . x_hat - |x_hat|^2
#
# matching the fp32 scan's "larger is better" l2 identity (2 q.x - |x|^2),
# so every executor ranks ADC scores the same way it ranks exact ones. As
# with int8, the ADC phase only *selects* rescore_k candidates; the exact
# fp32 gather-rescore ranks the final top-k.

PQ_N_CENTROIDS = 256
PQ_TRAIN_SAMPLE = 4096
PQ_TRAIN_ITERS = 10
# elements of one (rows, M, 256) distance tile in the device assignment
PQ_ASSIGN_TILE = 1 << 26


def default_pq_m(dim: int) -> int:
    """Default subspace count: the largest divisor of ``dim`` that is at
    most ``dim // 4`` (dsub >= 4 => codes are <= 1/16 of fp32 bytes)."""
    target = max(1, dim // 4)
    for m in range(target, 0, -1):
        if dim % m == 0:
            return m
    return 1


def _nearest_centroids(sub: np.ndarray, cents: np.ndarray) -> np.ndarray:
    """Nearest centroid of every (row, subspace): ``sub`` (n, M, dsub),
    ``cents`` (M, 256, dsub) -> (n, M) int64. Runs on the default device in
    row chunks of a power-of-two size (few compiled shapes) that bound the
    (chunk, M, 256) distance tile."""
    n, m, _ = sub.shape
    chunk = max(1, min(PQ_ASSIGN_TILE // (m * cents.shape[1]),
                       1 << max(n - 1, 0).bit_length()))
    cents_d = jnp.asarray(cents)
    out = np.empty((n, m), np.int64)
    for lo in range(0, n, chunk):
        part = sub[lo: lo + chunk]
        got = len(part)
        part = np.pad(part, [(0, chunk - got), (0, 0), (0, 0)])
        out[lo: lo + got] = np.asarray(
            _pq_assign(jnp.asarray(part), cents_d))[:got]
    return out


@jax.jit
def _pq_assign(sub, cents):
    # argmin |x - c|^2 == argmin |c|^2 - 2 x.c  (drop the |x|^2 term)
    dots = jnp.einsum("nmd,mcd->nmc", sub, cents,
                      precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.sum(cents * cents, axis=-1)[None] - 2.0 * dots
    return jnp.argmin(d2, axis=-1).astype(jnp.int32)


class PQCodebook:
    """Per-subspace k-means codebook with frozen-after-training encode.

    The codebook trains ONCE on an ingest sample (deterministic given
    ``seed``), then incrementally encodes every later row with the frozen
    centroids — the same watermark pattern the int8 mirror uses — so codes
    for already-ingested rows never change under DSM or further ingest.
    """

    def __init__(self, dim: int, m: Optional[int] = None, seed: int = 0):
        m = default_pq_m(dim) if m is None else int(m)
        if m <= 0 or dim % m != 0:
            raise ValueError(f"pq m {m} must divide dim {dim}")
        self.dim = dim
        self.m = m
        self.dsub = dim // m
        self.seed = seed
        self.centroids: Optional[np.ndarray] = None  # (m, 256, dsub) f32

    @property
    def trained(self) -> bool:
        return self.centroids is not None

    def _require_trained(self) -> None:
        if self.centroids is None:
            raise ValueError(
                "PQ codebook not trained: the codebook trains on the rows "
                "present at first use, so precision='pq' (and pq_lut/encode/"
                "decode) needs a non-empty store first")

    def train(self, rows: np.ndarray) -> None:
        """Lloyd k-means per subspace on (a sample of) ``rows``; empty
        clusters keep their previous centroid (the IVF trainer's rule).
        Every subspace runs its iterations in lockstep: one device
        assignment over all subspaces per iteration."""
        rows = np.atleast_2d(np.asarray(rows, dtype=np.float32))
        rng = np.random.default_rng(self.seed)
        n = len(rows)
        if n > PQ_TRAIN_SAMPLE:
            rows = rows[rng.choice(n, size=PQ_TRAIN_SAMPLE, replace=False)]
            n = PQ_TRAIN_SAMPLE
        k = PQ_N_CENTROIDS
        sub = rows.reshape(n, self.m, self.dsub)
        cents = np.stack([sub[rng.choice(n, size=k, replace=n < k), m]
                          for m in range(self.m)])          # (M, k, dsub)
        # flat (subspace, centroid) slot of every (row, subspace) pair
        base = np.arange(self.m, dtype=np.int64)[None, :] * k
        flat_sub = sub.reshape(-1, self.dsub)
        for _ in range(PQ_TRAIN_ITERS):
            slot = (base + _nearest_centroids(sub, cents)).ravel()
            counts = np.bincount(slot, minlength=self.m * k)
            sums = np.stack([np.bincount(slot, weights=flat_sub[:, j],
                                         minlength=self.m * k)
                             for j in range(self.dsub)], axis=1)
            nonempty = counts > 0
            flat_c = cents.reshape(-1, self.dsub)
            flat_c[nonempty] = (sums[nonempty]
                                / counts[nonempty, None]).astype(np.float32)
        self.centroids = cents

    def encode(self, rows: np.ndarray) -> np.ndarray:
        """Nearest-centroid codes, ``(n, M) uint8``."""
        self._require_trained()
        rows = np.atleast_2d(np.asarray(rows, dtype=np.float32))
        sub = rows.reshape(len(rows), self.m, self.dsub)
        return _nearest_centroids(sub, self.centroids).astype(np.uint8)

    def decode(self, codes: np.ndarray) -> np.ndarray:
        """Reconstruct ``(n, dim)`` fp32 rows from codes."""
        self._require_trained()
        codes = np.atleast_2d(np.asarray(codes))
        parts = [self.centroids[m][codes[:, m].astype(np.intp)]
                 for m in range(self.m)]
        return np.concatenate(parts, axis=1)

    def lut(self, queries: np.ndarray, metric: str) -> np.ndarray:
        """Per-query ADC tables, ``(nq, M, 256) float32`` (metric folded
        in — see the module docstring identity)."""
        self._require_trained()
        queries = np.atleast_2d(np.asarray(queries, dtype=np.float32))
        sub_q = queries.reshape(len(queries), self.m, self.dsub)
        dots = np.einsum("qmd,mcd->qmc", sub_q, self.centroids,
                         dtype=np.float32)
        if metric == "l2":
            cent_sq = (self.centroids * self.centroids).sum(axis=2)
            return (2.0 * dots - cent_sq[None]).astype(np.float32)
        return dots.astype(np.float32)

    def nbytes(self) -> int:
        """Codebook bytes (O(1) model state, reported separately from the
        per-row code bytes)."""
        if self.centroids is None:
            return 0
        return int(self.centroids.nbytes)


def int_exact_dot(a_i8, b_i8, dnums=(((1,), (1,)), ((), ())),
                  contract_dim: Optional[int] = None):
    """Dot of int8 code tensors as fp32 — THE shared scoring primitive of
    every int8 jnp twin (flat scan/gather, IVF tile scoring, the sharded
    local scan): one definition so the cross-executor "identical int8
    scores" contract can never drift.

    While every partial sum stays under 2^24 (``d * 127^2``; holds for any
    realistic dim) the f32 accumulation is bitwise the int32 result the
    Pallas kernels compute, but it rides the fast f32 GEMM on backends
    whose int8 path is a scalar loop (CPU XLA). Past the bound it falls
    back to true int32 accumulation. ``contract_dim`` defaults to the last
    axis of ``a_i8`` (pass it explicitly for exotic dnums)."""
    d = a_i8.shape[-1] if contract_dim is None else contract_dim
    if d * Q_MAX * Q_MAX < (1 << 24):
        return jax.lax.dot_general(
            a_i8.astype(jnp.float32), b_i8.astype(jnp.float32), dnums,
            preferred_element_type=jnp.float32)
    return jax.lax.dot_general(
        a_i8, b_i8, dnums,
        preferred_element_type=jnp.int32).astype(jnp.float32)
