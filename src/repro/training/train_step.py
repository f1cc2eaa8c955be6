"""Jitted training step: loss -> grads -> AdamW, with optional microbatch
gradient accumulation and optional int8-compressed cross-pod gradient sync.

Baseline (paper-faithful distribution): plain auto-SPMD — the batch is sharded
over ("pod","data"), XLA inserts the gradient all-reduces. The compressed
variant makes the ``pod`` axis *manual* (shard_map, data/model stay auto) and
reduces gradients across pods in int8 with per-leaf scales: 4x less DCN
traffic, the distributed-optimization trick for the multi-pod mesh.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models import loss_fn
from ..models.common import ArchConfig
from .optimizer import OptConfig, adamw_update


def int8_psum(tree, axis: str):
    """Quantize -> psum -> dequantize each leaf over ``axis`` (stochastic-free
    symmetric per-leaf scaling; bias-free in expectation for gradient noise)."""
    def one(g):
        g32 = g.astype(jnp.float32)
        scale = jnp.max(jnp.abs(g32)) / 127.0 + 1e-12
        # share a common scale across the axis so the psum is linear
        scale = jax.lax.pmax(scale, axis)
        q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
        summed = jax.lax.psum(q.astype(jnp.int32), axis)
        return (summed.astype(jnp.float32) * scale
                / jax.lax.axis_size(axis)).astype(g.dtype)
    return jax.tree.map(one, tree)


def make_train_step(cfg: ArchConfig, opt_cfg: OptConfig,
                    mesh: Optional[Mesh] = None,
                    accum_steps: int = 1,
                    cross_pod_int8: bool = False):
    """Returns step(params, opt_state, batch) -> (params, opt_state, metrics).

    ``accum_steps`` > 1 scans over microbatches (batch dim must divide).
    ``cross_pod_int8`` requires a mesh with a "pod" axis.
    """

    def grads_of(params, batch):
        if accum_steps == 1:
            return jax.value_and_grad(loss_fn)(params, batch, cfg, mesh)

        def micro(carry, mb):
            loss_acc, g_acc = carry
            l, g = jax.value_and_grad(loss_fn)(params, mb, cfg, mesh)
            return (loss_acc + l,
                    jax.tree.map(jnp.add, g_acc, g)), None

        mbs = jax.tree.map(
            lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                + x.shape[1:]), batch)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads), _ = jax.lax.scan(micro, (0.0, zero), mbs)
        inv = 1.0 / accum_steps
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    def base_step(params, opt_state, batch):
        loss, grads = grads_of(params, batch)
        params, opt_state, metrics = adamw_update(params, grads, opt_state,
                                                  opt_cfg)
        metrics["loss"] = loss
        return params, opt_state, metrics

    if not cross_pod_int8:
        return base_step

    if mesh is None or "pod" not in mesh.shape:
        raise ValueError("cross_pod_int8 requires a mesh with a 'pod' axis")

    def pod_step(params, opt_state, batch):
        # pod axis manual; data/model stay auto-sharded inside.
        def inner(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg, mesh)
            grads = int8_psum(grads, "pod")            # compressed DCN sync
            loss = jax.lax.pmean(loss, "pod")
            params, opt_state, metrics = adamw_update(params, grads, opt_state,
                                                      opt_cfg)
            metrics["loss"] = loss
            return params, opt_state, metrics

        # pod manual, data/model auto-sharded inside. Legacy XLA cannot
        # compile partial-manual regions (IsManualSubgroup check), so there
        # we go fully manual: the in_specs only partition over "pod", the
        # body is simply replicated across data/model — same numerics,
        # no intra-pod parallelism.
        if hasattr(jax.sharding, "AxisType"):
            manual = frozenset({"pod"})
        else:
            manual = frozenset(mesh.axis_names)
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P(), P("pod")),
            out_specs=(P(), P(), P()),
            axis_names=manual, check_vma=False,
        )(params, opt_state, batch)

    return pod_step
