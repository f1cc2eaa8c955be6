"""chip_smoke.py refuses to run anywhere but a TPU, and its plain reference
follows the directory semantics of MOVE and MERGE."""
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_refuses_a_cpu_backend(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_fails_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120,
                         env={"PATH": "/usr/bin:/bin"})
    assert run.returncode != 0
    assert '"ok"' not in run.stdout


def test_reference_scopes_follow_move_and_merge():
    paths = ["/a/", "/a/b/", "/a/b/c/", "/d/", "/d/e/", "/ab/"]
    vecs = np.eye(6, 8, dtype=np.float32)
    ref = chip_smoke.Reference(vecs, paths)
    assert ref.scope("/a/", True).tolist() == [0, 1, 2]
    assert ref.scope("/a/", False).tolist() == [0]
    ref.move("/a/b/", "/d/")                  # /a/b/... -> /d/b/...
    assert ref.scope("/d/", True).tolist() == [1, 2, 3, 4]
    assert ref.scope("/a/", True).tolist() == [0]
    ref.merge("/d/e/", "/ab/")                # /d/e/ contents -> /ab/
    assert ref.scope("/ab/", False).tolist() == [4, 5]
    s, i = ref.topk(np.eye(8, dtype=np.float32), [(2, "/d/", True),
                                                  (0, "/zz/", True)])
    assert i[0, 0] == 2 and s[0, 0] == 1.0
    assert (i[1] == -1).all() and np.isneginf(s[1]).all()


def test_recall_counts_k_boundary_ties():
    ref_i = np.array([[3, 1, 2], [-1, -1, -1]])
    ref_s = np.array([[0.9, 0.5, 0.5], [-np.inf] * 3], np.float32)
    empty = [[-1, -1, -1]]
    # id 7 ties the boundary score 0.5: as good as the missed id 2
    got_s = np.array([[0.9, 0.5, 0.5], [-np.inf] * 3], np.float32)
    assert chip_smoke.recall_at_k(np.array([[3, 1, 7]] + empty), got_s,
                                  ref_i, ref_s) == 1.0
    got_s = np.array([[0.9, 0.2, 0.1], [-np.inf] * 3], np.float32)
    assert chip_smoke.recall_at_k(np.array([[3, 7, 8]] + empty), got_s,
                                  ref_i, ref_s) == 1 / 3
