"""Pallas TPU kernel: packed scope-bitmask AND + popcount.

Used by the DSQ planner for selectivity estimation (choose gather- vs
scan-plan) and for combining scope masks (namespace intersection, exclusion)
directly on-device in packed uint32 form — 32x less HBM traffic than a bool
mask. Pure VPU/memory-bound; the roofline term is bytes, not FLOPs.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, words_ref, count_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = a_ref[...] & b_ref[...]
    words_ref[...] = w
    pc = jax.lax.population_count(w)
    acc_ref[...] += jnp.full((1, 1), jnp.sum(pc.astype(jnp.int32)),
                             jnp.int32)

    @pl.when(i == pl.num_programs(0) - 1)
    def _flush():
        count_ref[...] = acc_ref[...]


def _patch_kernel(m_ref, d_ref, op_ref, out_ref):
    m = m_ref[...]                       # (rows, block) uint32
    d = d_ref[...]                       # (1, block) uint32, broadcast
    op = op_ref[...]                     # (rows, 1) int32
    out_ref[...] = jnp.where(op > 0, m | d,
                             jnp.where(op < 0, m & ~d, m))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def bitmap_patch(masks: jax.Array, delta: jax.Array, ops: jax.Array,
                 block: int = 2048, interpret: bool = True) -> jax.Array:
    """Patch a batch of packed uint32 masks with one delta row in a single
    launch: row i becomes ``masks[i] | delta`` where ``ops[i] > 0``,
    ``masks[i] & ~delta`` where ``ops[i] < 0``, unchanged where 0.

    The DSM delta-maintenance primitive: after a MOVE/MERGE/REMOVE relocates
    aggregate S, every surviving cached scope mask on the vacated chain is
    AND-NOT-patched and every mask on the gaining chain OR-patched — word-wise
    on packed words, 32x less traffic than dense bool masks, instead of
    re-resolving the scopes from scratch.

    masks: (rows, n_words) uint32; delta: (1, n_words) uint32;
    ops: (rows, 1) int32. n_words % block == 0 (ops.py pads with zero words —
    OR/AND-NOT neutral).
    """
    rows, n = masks.shape
    assert n % block == 0
    return pl.pallas_call(
        _patch_kernel,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((rows, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((rows, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.uint32),
        interpret=interpret,
    )(masks, delta, ops)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def mask_and_popcount(a: jax.Array, b: jax.Array, block: int = 2048,
                      interpret: bool = True) -> Tuple[jax.Array, jax.Array]:
    """AND two packed uint32 masks; returns (words, total_popcount).

    a, b: (n_words,) uint32, n_words % block == 0 (ops.py pads with zeros —
    zero words are AND-neutral for the count).
    """
    (n,) = a.shape
    assert n % block == 0
    words, count = pl.pallas_call(
        _kernel,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.uint32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.int32)],
        interpret=interpret,
    )(a, b)
    return words, count[0, 0]
