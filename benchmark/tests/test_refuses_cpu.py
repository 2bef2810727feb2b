"""Off the chip there is no result: non-zero exit, no metric line."""

import os
import subprocess
import sys

from benchmark import cells


def test_run_refuses_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "run.py"), "--workload",
         "gpt2_124m.steady", "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=cells.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout
    assert not os.path.exists(os.path.join(cells.HERE, "out",
                                           "gpt2_124m.steady"))


def test_unknown_cell_is_refused():
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "run.py"), "--workload",
         "no_such.cell"], cwd=cells.ROOT, capture_output=True, text=True,
        timeout=60, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and '"metrics"' not in proc.stdout
