"""``BENCHMARK.json`` keeps to the benchmark's contract: allowed keys and
characters, and every configuration, traffic mix and metric found by name
as a file of its own."""
import benchpath  # noqa: F401

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_names_and_text(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        assert NAME.match(e["name"]), e["name"]
        extra = set(e) - KEYS[section]
        assert extra <= {"workloads"} and (not extra
                                           or section in ("end_to_end",
                                                          "per_layer"))
        assert KEYS[section] <= set(e)
        for k in ("why", "layer", "source"):
            if k in e and section != "end_to_end" and section != "per_layer":
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        if section == "per_layer":
            assert 1 <= len(e["layer"]) <= 200
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        if section == "end_to_end":
            assert e["source"] in ("host_clock", "device_trace")
            assert 0 < e["bound"] <= 0.25


def test_everything_is_found_by_name():
    bench = ROOT / "bench"
    cfgs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"]: w for w in SPEC["workloads"]}
    for w in SPEC["workloads"]:
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        traffic = bench / "traffic" / f"{w['traffic']}.json"
        loop = json.loads(traffic.read_text())["loop"]
        assert NAME.match(loop) and (bench / "loops" / f"{loop}.py").is_file()
        assert len(w["why"]) <= 200
        reported = [m for m in SPEC["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in reported} and len(reported) > 1
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in SPEC["per_layer"])
    assert {m["name"] for m in SPEC["end_to_end"]} >= {"setup_s"}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (bench / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in moved.get("workloads", [w])
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
