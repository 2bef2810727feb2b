"""Finding a cell's files by the names in BENCHMARK.json.

Nothing here knows a cell, a configuration, a traffic mix or a metric
by name: a later PR adds entries to BENCHMARK.json and files beside the
existing ones, and edits nothing.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py` as a module (the file name may hold
    dots, so this does not go through `import`)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_parked() -> dict:
    """`parked.json`: entries BENCHMARK.json had and may get back (cells
    whose end-to-end metric holds no admissible bound yet).  They can be
    run for study; the driver's check never sees them."""
    return _load_json(os.path.join(HERE, "parked.json"))


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one run needs to know about cell `name`."""
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        parked = load_parked()
        entry = next((w for w in parked["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        # a parked cell reports what it reported when it was measured
        bench = {**bench, **{
            k: [{**m, "workloads": [name]} for m in bench[k] + parked[k]
                if m["name"] in parked["reports"][name]]
            for k in ("end_to_end", "per_layer")}}
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(HERE, "traffic",
                                      entry["traffic"] + ".json"))
    chips = int(entry["chips"])
    batches = config["train"]["global_batch_size"]
    if str(chips) not in batches:
        raise KeyError(f"{cfg_entry['file']} gives no global batch for "
                       f"{chips} chip(s)")
    return {
        "name": name, "chips": chips, "config_name": entry["config"],
        "traffic_name": entry["traffic"], "config": config,
        "traffic": traffic, "global_batch": int(batches[str(chips)]),
        "seq_len": int(config["train"]["seq_len"]),
        "end_to_end": [m for m in bench["end_to_end"]
                       if _applies(m, name)],
        "per_layer": [m for m in bench["per_layer"] if _applies(m, name)],
        "run_seconds": bench["run_seconds"],
    }
