"""Mixture-of-Experts FFN: dropless-style token-choice routing with two
execution strategies.

* ``ep_shardmap`` (default): expert-parallel placement over the ``model`` mesh
  axis via shard_map. Activations are token-sharded over the data axes and
  replicated across the model axis; every device locally groups the hits for
  the experts *it owns* (local sort -> capacity slots -> grouped matmul ->
  weighted scatter-add) and a single psum over ``model`` combines expert
  contributions. All routing logic is device-local (tiny HLO, no global sort
  collectives); communication is one activation all-reduce, identical in shape
  to the dense-TP FFN case.
* ``dense_tp``: computes every expert for every token with d_ff sharded over
  ``model`` and mask-combines — E/topk x more FLOPs, kept as a compile-safe
  fallback and as the roofline "bad baseline" for §Perf.

Top-k weights are renormalized; capacity C = ceil(T_local * k / E * cf) drops
overflow tokens per expert (standard GShard-style behaviour).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .layers import Spec


def moe_schema(cfg) -> Dict[str, Spec]:
    D = cfg.d_model
    E = cfg.n_experts
    fe = cfg.moe_d_ff or cfg.d_ff
    s = {
        "router": Spec((D, E), ("embed", None), "small"),
        "w_gate": Spec((E, D, fe), ("experts", "embed_fsdp", "expert_mlp")),
        "w_up": Spec((E, D, fe), ("experts", "embed_fsdp", "expert_mlp")),
        "w_down": Spec((E, fe, D), ("experts", "expert_mlp", "embed_fsdp")),
    }
    if cfg.n_shared_experts > 0:
        fs = cfg.n_shared_experts * fe
        s["shared"] = {
            "w_gate": Spec((D, fs), ("embed_fsdp", "mlp")),
            "w_up": Spec((D, fs), ("embed_fsdp", "mlp")),
            "w_down": Spec((fs, D), ("mlp", "embed_fsdp")),
        }
    return s


def _route(xf: jax.Array, router: jax.Array, top_k: int
           ) -> Tuple[jax.Array, jax.Array]:
    logits = (xf.astype(jnp.float32) @ router.astype(jnp.float32))
    weights, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights, idx


def _local_expert_ffn(xf, weights, idx, w1, w2, w3, e_base: int,
                      capacity: int, act: str):
    """Grouped FFN over locally-owned experts [e_base, e_base+E_loc).

    xf (T, D); weights/idx (T, K); w1/w2 (E_loc, D, F); w3 (E_loc, F, D).
    Pure device-local ops. Returns (T, D) partial output.
    """
    T, D = xf.shape
    K = idx.shape[1]
    E_loc = w1.shape[0]
    fe = idx.reshape(-1) - e_base                       # (T*K,)
    fw = weights.reshape(-1)
    owned = (fe >= 0) & (fe < E_loc)
    sort_key = jnp.where(owned, fe, E_loc).astype(jnp.int32)
    order = jnp.argsort(sort_key)                       # stable
    se = sort_key[order]
    st = order // K                                     # source token
    sw = fw[order]
    counts = jnp.bincount(se, length=E_loc + 1)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)])[:-1]
    pos = jnp.arange(T * K) - starts[se]
    keep = (se < E_loc) & (pos < capacity)
    slot = jnp.where(keep, se * capacity + pos, E_loc * capacity)
    # dispatch: scatter token rows into (E_loc*C [+1 drop row], D)
    vals = jnp.where(keep[:, None], xf[st], 0).astype(xf.dtype)
    xg = jnp.zeros((E_loc * capacity + 1, D), xf.dtype).at[slot].add(vals)
    xe = xg[:-1].reshape(E_loc, capacity, D)
    h1 = jnp.einsum("ecd,edf->ecf", xe, w1)
    if act == "swiglu":
        h = jax.nn.silu(h1) * jnp.einsum("ecd,edf->ecf", xe, w2)
    else:
        h = jax.nn.gelu(h1)
    ye = jnp.einsum("ecf,efd->ecd", h, w3)              # (E_loc, C, D)
    # combine: gather each hit's expert output, weight, scatter-add per token
    yflat = ye.reshape(E_loc * capacity, D)
    picked = jnp.where(keep[:, None], yflat[jnp.minimum(slot, E_loc * capacity - 1)], 0)
    y = jnp.zeros((T, D), jnp.float32).at[st].add(
        picked.astype(jnp.float32) * sw[:, None])
    return y.astype(xf.dtype)


def moe_apply(p: Dict[str, jax.Array], x: jax.Array, cfg,
              mesh: Optional[Mesh] = None) -> jax.Array:
    """x (B, S, D) -> (B, S, D). Routed experts + optional shared experts."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    act = cfg.act
    xf = x.reshape(B * S, D)

    model_size = 1
    if mesh is not None and "model" in mesh.shape:
        model_size = mesh.shape["model"]
    use_ep = (cfg.moe_impl == "ep_shardmap" and mesh is not None
              and model_size > 1 and E % model_size == 0
              and (B * S) % _data_size(mesh) == 0)   # e.g. B=1 decode falls back

    if cfg.moe_impl == "dense_tp" :
        weights, idx = _route(xf, p["router"], K)
        h1 = jnp.einsum("td,edf->tef", xf, p["w_gate"])
        if act == "swiglu":
            h = jax.nn.silu(h1) * jnp.einsum("td,edf->tef", xf, p["w_up"])
        else:
            h = jax.nn.gelu(h1)
        ye = jnp.einsum("tef,efd->ted", h, p["w_down"])
        comb = jnp.zeros((xf.shape[0], E), ye.dtype)
        comb = comb.at[jnp.arange(xf.shape[0])[:, None], idx].add(
            weights.astype(ye.dtype))
        y = jnp.einsum("ted,te->td", ye, comb)
    elif use_ep:
        E_loc = E // model_size
        t_loc = max(1, (B * S) // _data_size(mesh))
        capacity = int(math.ceil(t_loc * K / E * cfg.capacity_factor))
        data_axes = tuple(a for a in mesh.axis_names if a != "model")

        def shard_fn(xl, router, w1, w2, w3):
            weights, idx = _route(xl, router, K)
            rank = jax.lax.axis_index("model")
            y = _local_expert_ffn(xl, weights, idx, w1, w2, w3,
                                  e_base=rank * E_loc, capacity=capacity,
                                  act=act)
            return jax.lax.psum(y, "model")

        y = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(data_axes, None), P(None, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=P(data_axes, None),
            check_vma=False,
        )(xf, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    else:
        # single-device / replicated-experts local path
        weights, idx = _route(xf, p["router"], K)
        capacity = int(math.ceil(xf.shape[0] * K / E * cfg.capacity_factor))
        y = _local_expert_ffn(xf, weights, idx, p["w_gate"], p["w_up"],
                              p["w_down"], e_base=0, capacity=capacity,
                              act=act)

    if cfg.n_shared_experts > 0:
        sp = p["shared"]
        if act == "swiglu":
            ys = (jax.nn.silu(xf @ sp["w_gate"]) * (xf @ sp["w_up"])) @ sp["w_down"]
        else:
            ys = jax.nn.gelu(xf @ sp["w_up"]) @ sp["w_down"]
        y = y + ys
    return y.reshape(B, S, D)


def _data_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names
                        if a != "model"]))
