#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``), its traffic
mix (``bench/traffic/<traffic>.json``, parameters of the load generator
``bench/loops/<loop>.py`` that its ``loop`` names) and its metrics (one
reader each in ``bench/metrics/<name>.py``) are all found by name from
``BENCHMARK.json`` at the root of the checkout. The last line of standard output is the result
object; with ``--trace 0`` its metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics. The compared numbers and their
limits close standard error and the result line. Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                     # noqa: E402
import json                                         # noqa: E402
import sys                                          # noqa: E402
from pathlib import Path                            # noqa: E402

BENCH = Path(__file__).resolve().parent


def load_cell(name: str):
    from benchlib.cell import Cell
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r} (have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((BENCH.parent / cfg_entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())

    def applies(m):
        return name in m.get("workloads", [name])
    return Cell(name=name, config=config, traffic=traffic,
                chips=int(w["chips"]),
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    from benchlib import cell as cell_mod
    cell = load_cell(args.workload)
    try:
        result = cell_mod.run(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    except cell_mod.NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    checks = result["checks"]
    for k, v in checks.items():
        print(f"check {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
