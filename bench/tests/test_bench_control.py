"""The control — the reference one precision step down, put in the
program's place — fails the comparison, at the cells' width on a corpus a
test can hold, on three seeds."""
import benchpath  # noqa: F401

import pytest

from benchlib import cell as cell_mod, control, twin
from tinycell import tiny_cell


@pytest.mark.parametrize("name", ["wiki-dir.zipf-dsm-closed",
                                  "arxiv-dir.broad-closed"])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_control_fails(name, seed):
    cell = tiny_cell(name, entries=4000)
    corpus = twin.build_corpus(cell.config)
    vectors = twin.device_vectors(corpus, seed)
    loop = cell_mod.load_loop(cell.traffic["loop"])
    stream = loop.build_stream(cell.config, cell.traffic, corpus, seed)
    ops, is_dsm, qs = loop.timeline(stream, 400)
    qvecs = twin.query_vectors(corpus, vectors, stream.entries, seed)
    v = control.judge_control(corpus, vectors, qvecs, ops, is_dsm, qs)
    limits = cell.config["limits"]
    assert v.checked > 100
    assert any(n > limits[k] for k, n in v.numbers().items()), v
