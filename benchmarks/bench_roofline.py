"""§Roofline: render the per-(arch × shape × mesh) roofline table from the
dry-run artifacts in results/dryrun/*.json (see repro/launch/dryrun.py).

Terms (TPU v5e constants, DESIGN.md §Roofline):
  compute    = FLOPs_global / (chips · 197e12)
  memory     = bytes_global / (chips · 819e9)
  collective = link_bytes_per_device · multiplier / 50e9
FLOPs/bytes come from the L1/L2 unroll extrapolation (scan bodies are counted
once by XLA cost analysis — measured and documented); link bytes from the HLO
collective parser.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional

from repro.analysis import roofline as RL
from repro.configs import ARCHS, SHAPES, get_arch

RESULTS = Path(__file__).resolve().parents[1] / "results" / "dryrun"


def load_cells(results_dir: Path = RESULTS, mesh: str = "16x16",
               tag: Optional[str] = None) -> List[Dict]:
    cells = []
    for p in sorted(results_dir.glob("*.json")):
        rec = json.loads(p.read_text())
        if rec.get("mesh") != mesh:
            continue
        stem_tag = p.stem.split(mesh)[-1].lstrip("_")
        if (tag or "") != stem_tag:
            continue
        cells.append(rec)
    return cells


def cell_terms(rec: Dict) -> Optional[RL.RooflineTerms]:
    """Roofline terms: compute + collective from the dry-run HLO; memory from
    the analytic TPU-fusion model (the unfused-CPU HLO bytes are reported
    separately as ``hlo_memory_s``, an upper bound)."""
    if rec.get("skipped") or not rec.get("ok"):
        return None
    src = rec.get("extrapolated") or rec.get("full")
    chips = rec["full"]["chips"]
    metrics = {
        "flops": src.get("flops_global", src.get("flops", 0.0) * chips),
        "bytes": src.get("bytes_global", src.get("bytes", 0.0) * chips),
        "link_bytes": src.get("link_bytes", 0.0),
    }
    if rec["arch"] in ARCHS and rec["shape"] in SHAPES:
        analytic = RL.hbm_bytes_analytic(get_arch(rec["arch"]),
                                         SHAPES[rec["shape"]])
        metrics["hlo_bytes"] = metrics["bytes"]
        metrics["bytes"] = analytic
    t = RL.terms_from(metrics, chips, model_flops=rec.get("model_flops", 0))
    t.hlo_memory_s = metrics.get("hlo_bytes", 0.0) / (chips * RL.HBM_BW)
    return t


def run(mesh: str = "16x16", tag: Optional[str] = None) -> List[Dict]:
    rows = []
    for rec in load_cells(mesh=mesh, tag=tag):
        name = f"roofline/{rec['arch']}/{rec['shape']}/{mesh}"
        if rec.get("skipped"):
            rows.append({"name": name, "us_per_call": 0.0,
                         "derived": f"SKIP:{rec['reason'][:40]}"})
            continue
        if not rec.get("ok"):
            rows.append({"name": name, "us_per_call": 0.0,
                         "derived": f"FAIL:{rec.get('error', '')[:60]}"})
            continue
        t = cell_terms(rec)
        rows.append({
            "name": name,
            "us_per_call": t.bound_s * 1e6,     # roofline-bound step time
            "derived": (f"compute_s={t.compute_s:.3e};"
                        f"memory_s={t.memory_s:.3e};"
                        f"hlo_memory_s={getattr(t, 'hlo_memory_s', 0):.3e};"
                        f"collective_s={t.collective_s:.3e};"
                        f"dominant={t.dominant};"
                        f"useful={t.useful_ratio:.3f};"
                        f"frac={t.roofline_fraction:.3f}"),
        })
    return rows


def markdown_table(mesh: str = "16x16", tag: Optional[str] = None) -> str:
    lines = ["| arch | shape | compute (s) | memory (s) | HLO-mem (s) | "
             "collective (s) | dominant | MODEL/HLO flops | roofline frac |",
             "|---|---|---|---|---|---|---|---|---|"]
    for rec in load_cells(mesh=mesh, tag=tag):
        if rec.get("skipped"):
            lines.append(f"| {rec['arch']} | {rec['shape']} | — | — | — | — | "
                         f"skipped | — | — |")
            continue
        if not rec.get("ok"):
            lines.append(f"| {rec['arch']} | {rec['shape']} | FAIL | | | | | | |")
            continue
        t = cell_terms(rec)
        lines.append(
            f"| {rec['arch']} | {rec['shape']} | {t.compute_s:.3e} | "
            f"{t.memory_s:.3e} | {getattr(t, 'hlo_memory_s', 0):.3e} | "
            f"{t.collective_s:.3e} | {t.dominant} | "
            f"{t.useful_ratio:.2f} | {t.roofline_fraction:.2f} |")
    return "\n".join(lines)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from .common import emit
    emit(run())
