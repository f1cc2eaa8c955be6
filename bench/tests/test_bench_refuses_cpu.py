"""``run.py`` refuses a CPU backend: it exits non-zero and prints no
result line."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_run_refuses_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "wiki-dir.zipf-dsm-closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[-1]\
        .startswith("{")
    assert "not a TPU" in p.stderr
