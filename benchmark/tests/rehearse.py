"""CPU rehearsal of a cell at `nano` size — control flow only.

    JAX_PLATFORMS=cpu python benchmark/tests/rehearse.py \
        --workload gpt2_124m.steady --seconds 3 --trace 0

The benchmark itself refuses anything but the chip.  This script, and
only this script, lifts that refusal and shrinks the configuration so
that paths, arguments and the window's bookkeeping can be tried where
there is no chip.  Every number it prints is a CPU number at a toy size
and means nothing; the device says `cpu-rehearsal` so that none can be
mistaken for a measurement.  (`XLA_FLAGS=
--xla_force_host_platform_device_count=4` rehearses a four-chip cell.)
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import cells, worker  # noqa: E402

NANO = dict(vocab_size=512, n_layer=2, n_head=2, n_embd=128,
            n_positions=128, n_ctx=128)


def _no_chip(chips: int) -> dict:
    import jax

    assert jax.devices()[0].platform == "cpu", "rehearsal is for the CPU"
    assert len(jax.devices()) == chips, (len(jax.devices()), chips)
    return {"platform": "cpu-rehearsal", "kind": "TPU v5 lite",
            "count": chips}


def _nano_cell(load):
    def load_cell(name, root=cells.ROOT):
        cell = load(name, root)
        cell["config"].update(NANO)
        cell["config"]["train"]["seq_len"] = cell["seq_len"] = 128
        cell["global_batch"] = 8
        cell["config"]["correct"].update(loss_rtol=0.05, grad_norm_rtol=0.2,
                                         loss_band=[0.0, 100.0])
        return cell
    return load_cell


def patch() -> None:
    worker.require_tpu = _no_chip
    cells.load_cell = _nano_cell(cells.load_cell)


if __name__ == "__main__":
    patch()
    if len(sys.argv) == 2 and sys.argv[1].endswith(".json"):
        # started by the agent as the cell's worker (see below)
        if os.getenv("DWT_RESTART_COUNT") == \
                os.getenv("REHEARSE_COLD_GEN", "never"):
            # force the cold-resume path: this generation finds no cache
            import shutil

            shutil.rmtree(os.environ["JAX_COMPILATION_CACHE_DIR"],
                          ignore_errors=True)
        sys.exit(worker.main())
    from benchmark import run

    _load = cells.load_module

    def load_module(kind, name):
        mod = _load(kind, name)
        if (kind, name) == ("drivers", "elastic_cli"):
            # the agent starts the worker as a new process: make that
            # process this script, so the patches hold there too
            mod.WORKER_SCRIPT = os.path.abspath(__file__)
        return mod

    cells.load_module = load_module
    sys.exit(run.main())
