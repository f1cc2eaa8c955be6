"""Roofline accounting from compiled dry-run artifacts.

Three terms per (arch × shape × mesh) cell, TPU v5e peaks (the dry-run's
target chip; :data:`DEVICE_PEAKS` keeps every chip's published peaks):

    compute    = HLO_FLOPs      / (chips × 197e12 FLOP/s bf16)
    memory     = HLO_bytes      / (chips × 819e9  B/s HBM)
    collective = coll_bytes_dev / (50e9 B/s per-link ICI)

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes, an HLO-text parser for
collective buffer bytes (cost_analysis does not expose them). Two caveats this
module owns:

1. **scan bodies are counted once** by XLA's cost analysis. The dry-run
   therefore also compiles unrolled 1-layer and 2-layer variants of each cell;
   ``extrapolate`` turns (L1, L2) into per-layer deltas and reconstructs the
   full-depth totals:  total(L) = cost(L1) + (L-1) · (cost(L2) − cost(L1)).
2. HLO is one per-device SPMD program: parsed collective bytes are per-device;
   with the formula above the chip count cancels, leaving bytes/link_bw.
   all-reduce gets a 2x ring factor ((2(n-1)/n) ≈ 2).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""
    bf16_flops: float          # FLOP/s
    int8_ops: float            # OP/s
    hbm_bytes_per_s: float     # HBM bandwidth
    hbm_bytes: float           # HBM capacity
    source: str


_V5E = DevicePeaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
                   hbm_bytes=16e9,
                   source="Google Cloud documentation, 'TPU v5e'")

# keyed by ``jax.Device.device_kind``; JAX names a v5e "TPU v5 lite"
DEVICE_PEAKS: Dict[str, DevicePeaks] = {"TPU v5 lite": _V5E}


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks of ``device_kind``; an unknown kind is an error, never a
    stand-in chip's numbers."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add it to DEVICE_PEAKS "
            f"with its source") from None


PEAK_FLOPS = _V5E.bf16_flops   # the dry-run's target chip
HBM_BW = _V5E.hbm_bytes_per_s
LINK_BW = 50e9             # bytes/s per ICI link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?P<shape>[^=]*?)\s*"
    r"(?P<op>all-reduce-start|all-reduce|all-gather-start|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(")
_SHAPE_RE = re.compile(r"([a-z]+[0-9a-z]*)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, float]:
    """Per-device collective buffer bytes by op kind (+ op counts)."""
    bytes_by, count_by = {}, {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op").replace("-start", "")
        if op.endswith("-done"):
            continue
        b = _shape_bytes(m.group("shape"))
        bytes_by[op] = bytes_by.get(op, 0) + b
        count_by[op] = count_by.get(op, 0) + 1
    link_bytes = sum(b * (2.0 if op == "all-reduce" else 1.0)
                     for op, b in bytes_by.items())
    out = {f"bytes_{k}": v for k, v in bytes_by.items()}
    out.update({f"count_{k}": v for k, v in count_by.items()})
    out["link_bytes"] = link_bytes
    return out


def cost_summary(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis() or {}
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
    }
    try:
        mem = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                out[attr] = float(v)
    except Exception:  # pragma: no cover - backend-specific
        pass
    out.update(parse_collectives(compiled.as_text()))
    return out


def extrapolate(l1: Dict[str, float], l2: Dict[str, float],
                n_layers: int, keys=("flops", "bytes", "link_bytes")
                ) -> Dict[str, float]:
    """total(L) = L1 + (L-1) * (L2 - L1), per metric."""
    out = {}
    for k in keys:
        a, b = l1.get(k, 0.0), l2.get(k, 0.0)
        delta = max(b - a, 0.0)
        out[k] = a + (n_layers - 1) * delta
        out[f"per_layer_{k}"] = delta
    return out


@dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    chips: int
    model_flops: float = 0.0
    hlo_flops: float = 0.0
    hlo_memory_s: float = 0.0   # unfused-HLO upper bound (CPU backend)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """compute_term / max(all terms): 1.0 = perfectly compute-bound."""
        return self.compute_s / self.bound_s if self.bound_s else 0.0


def terms_from(metrics: Dict[str, float], chips: int,
               model_flops: float = 0.0) -> RooflineTerms:
    return RooflineTerms(
        compute_s=metrics.get("flops", 0.0) / (chips * PEAK_FLOPS),
        memory_s=metrics.get("bytes", 0.0) / (chips * HBM_BW),
        collective_s=metrics.get("link_bytes", 0.0) / LINK_BW,
        chips=chips,
        model_flops=model_flops,
        hlo_flops=metrics.get("flops", 0.0),
    )


def model_flops_estimate(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D (train) / 2·N·D (inference), N = active params."""
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n_active * tokens


def hbm_bytes_analytic(cfg, shape) -> float:
    """Analytic *global* HBM traffic per step assuming TPU-level fusion.

    The dry-run's ``bytes accessed`` comes from the un-fused CPU HLO and
    overstates HBM traffic by the fusion factor; this closed-form model is the
    TPU-expected traffic and is what the §Roofline memory term reports (the
    HLO number is kept as an upper bound / fusion-headroom signal).

    train:   params 2B read + grads 2B written + 2 moments f32 read+write
             + params f32-ish write  (ZeRO-sharded, so global = N * 22B)
             + per-layer activation streams (~12 D-wide read/writes per token,
             x2 for the remat recompute) + logits f32 read+write
    prefill: params read once + ~8 D-wide streams per token per layer
             + KV cache write
    decode:  params read + full KV cache read + small vectors
    """
    N = cfg.param_count()
    D = cfg.d_model
    L = cfg.n_layers + cfg.encoder_layers
    B = shape.global_batch
    S = shape.seq_len
    kvb = 2 * cfg.kv_heads * cfg.hd * 2          # k+v bytes/token/layer (bf16)
    if shape.kind == "train":
        tokens = B * S
        act = tokens * D * 2 * 12 * L * 2        # streams x remat recompute
        logits = 2 * tokens * cfg.vocab_size * 4
        return N * 22.0 + act + logits
    if shape.kind == "prefill":
        tokens = B * S
        act = tokens * D * 2 * 8 * L
        kv = tokens * kvb * cfg.n_layers
        return N * 2.0 + act + kv
    # decode: one token/seq; attention layers read the whole cache
    cache_read = B * S * kvb * cfg.n_layers if not cfg.attn_free else 0
    ssm_state = 0
    if cfg.attn_free or cfg.hybrid:
        d_in = cfg.ssm_expand * D
        ssm_state = 2 * B * cfg.n_layers * (d_in // max(cfg.ssm_head_dim, 1)
                                            * cfg.ssm_head_dim * cfg.ssm_state
                                            ) * 4
    return N * 2.0 + cache_read + ssm_state + B * D * 2 * 8 * L
