"""A cell of ``BENCHMARK.json`` shrunk to a size the CPU tests can hold:
few entries and directories, the cell's own width, traffic and limits."""
import benchpath  # noqa: F401

import time

from run import load_cell


def tiny_cell(name: str, entries: int = 3000, dirs: int = 500):
    cell = load_cell(name)
    cell.config["entries"] = entries
    for ns in cell.config["namespaces"]:
        ns["dirs"] = min(ns["dirs"], dirs)
    cell.traffic["clients"] = 16
    cell.traffic["pool"] = min(cell.traffic["pool"], 256)
    return cell


def run_tiny(name: str, seed: int, seconds: float = 2.0, **kw) -> dict:
    from benchlib import cell as cell_mod
    return cell_mod.run(tiny_cell(name, **kw), seed, seconds, False,
                        time.perf_counter(), require_tpu=False)
