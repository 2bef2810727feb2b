"""Thin CI wrapper for graftlint (`python tools/lint.py [args...]`).

One JSON line on stdout, details on stderr, non-zero exit on
findings.  `--changed` is the fast pre-commit mode
(git-changed .py files through the jax-free
ast+protocol+concurrency+schema engines); `--format sarif` swaps the
stdout line for a SARIF 2.1.0 document for CI annotation;
`--update-lock` regenerates analysis/schema.lock.json from the
extracted wire surface.  Exists so CI configs and the dryrun driver
can call a stable path without knowing the package layout; all logic
lives in dlrover_wuqiong_tpu/analysis/__main__.py.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from dlrover_wuqiong_tpu.analysis.__main__ import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
