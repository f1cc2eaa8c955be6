"""A run drives the served path and its comparison; with the timed path
broken underneath, ``correct`` comes out false. Runs on the CPU at a small
size, past the harness's look for a chip."""
import benchpath  # noqa: F401

import numpy as np
import pytest

from tinycell import run_tiny

CELLS = ["wiki-dir.zipf-dsm-closed", "arxiv-dir.broad-closed"]


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    from benchlib import cell as cell_mod
    monkeypatch.setattr(cell_mod, "enable_compile_cache", lambda root: "off")


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(name):
    res = run_tiny(name, seed=2**31 + 3)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(res)[-1] == "checks"


def _alter_scores(monkeypatch):
    """An answer altered where it is produced: the scan and gather
    launches return one score a little off."""
    from repro.vectordb import flat

    def bump(fn):
        def wrapped(*a, **k):
            s, i = fn(*a, **k)
            s = np.array(s, copy=True)
            s[0, 0] += 1e-4
            return s, i
        return wrapped
    monkeypatch.setattr(flat.FlatExecutor, "search",
                        bump(flat.FlatExecutor.search))
    monkeypatch.setattr(flat.FlatExecutor, "search_multi",
                        bump(flat.FlatExecutor.search_multi))


def _alter_ids(monkeypatch):
    """An answer altered where it is produced: the first id of every
    launch replaced by another row."""
    from repro.vectordb import flat

    def swap(fn):
        def wrapped(self, *a, **k):
            s, i = fn(self, *a, **k)
            i = np.array(i, copy=True)
            if i[0, 0] >= 0:
                i[0, 0] = (i[0, 0] + 1) % len(self.store)
            return s, i
        return wrapped
    monkeypatch.setattr(flat.FlatExecutor, "search",
                        swap(flat.FlatExecutor.search))
    monkeypatch.setattr(flat.FlatExecutor, "search_multi",
                        swap(flat.FlatExecutor.search_multi))


def _drop_half(monkeypatch):
    """Half of each batch left out: its requests come back empty."""
    from repro.vectordb import database

    orig = database.DirectoryVectorDB.dsq_batch

    def wrapped(self, *a, **k):
        out = orig(self, *a, **k)
        for r in out[len(out) // 2:]:
            r.ids = np.full_like(r.ids, -1)
            r.scores = np.full_like(r.scores, -np.inf)
        return out
    monkeypatch.setattr(database.DirectoryVectorDB, "dsq_batch", wrapped)


def _skip_dsm(monkeypatch):
    """Structural updates acknowledged but never applied: the state is
    returned unchanged."""
    from repro.core import DSMBatchResult, DSMStats
    from repro.vectordb import database

    def ack(self, ops, namespace="fs", stats=None, max_workers=4):
        return DSMBatchResult(results=[None] * len(ops),
                              errors=[None] * len(ops), stats=DSMStats())
    monkeypatch.setattr(database.DirectoryVectorDB, "dsm_batch", ack)


FAULTS = {"alter_scores": (_alter_scores, CELLS),
          "alter_ids": (_alter_ids, CELLS),
          "drop_half": (_drop_half, CELLS),
          "skip_dsm": (_skip_dsm, CELLS[:1])}


@pytest.mark.parametrize("fault,name", [(f, n) for f, (_, cells)
                                        in FAULTS.items() for n in cells])
def test_broken_path_is_not_correct(fault, name, monkeypatch):
    FAULTS[fault][0](monkeypatch)
    res = run_tiny(name, seed=11)
    assert not res["correct"], res["checks"]
