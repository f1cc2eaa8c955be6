#!/usr/bin/env python3
"""Read the control's numbers for a cell, at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--queries N]

For each seed it builds the run's corpus and request stream and puts the
control (``benchlib.control``: the plain reference one precision step down)
in the program's place: every query of the stream is answered by the
control on the tree as the ops before it left it, and judged against the
reference exactly as a run judges the program. It prints one JSON line of
compared numbers per seed; a sound comparison calls each seed wrong. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=2000,
                    help="how many of the stream's queries to lay out")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    from benchlib import cell as cell_mod, control, twin
    from run import load_cell
    cell = load_cell(args.workload)
    cell_mod.enable_compile_cache(BENCH.parent)
    loop = cell_mod.load_loop(cell.traffic["loop"])
    for seed in (int(s) for s in args.seeds.split(",")):
        corpus = twin.build_corpus(cell.config)
        vectors = twin.device_vectors(corpus, seed)
        stream = loop.build_stream(cell.config, cell.traffic, corpus, seed)
        ops, is_dsm, qs = loop.timeline(stream, args.queries)
        qvecs = twin.query_vectors(corpus, vectors, stream.entries, seed)
        v = control.judge_control(corpus, vectors, qvecs, ops, is_dsm, qs)
        limits = cell.config["limits"]
        numbers = v.numbers()
        print(json.dumps({"seed": seed, "checked": v.checked, **numbers,
                          "fails": [k for k in numbers
                                    if numbers[k] > limits[k]]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
