"""How `data/steady_scoped_2steps.*.json.gz` was recorded (PR 24).

    chiprun -- python3 benchmark/tests/record.py --workload \
        gpt2_124m.steady --seed 24 --out chiprun_out/record

Runs the cell once traced through `run.main()` (so the result line is
printed as ever), then, still in the process that ran the Trainer,
writes the pair the tests read: the first two traced optimizer steps of
the compact trace (as `steady_2steps.json.gz` was cut in PR 23) and, for
the instruction names in them, the scope table of the step program that
ran (`program.scope_table()`).  On the chip only, like `run.py`.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program, run, xtrace  # noqa: E402


def cut(trace: dict, steps: int = 2) -> dict:
    """Device 0's first `steps` train-step modules and the ops inside
    them, times from 20 us before the first."""
    dev = xtrace.device_ids(trace)[0]
    mods = xtrace.step_modules(trace, dev)[:steps]
    t0 = mods[0][1] - 20000
    hi = mods[-1][1] + mods[-1][2]

    def shift(e):
        return [e[0], e[1] - t0, e[2]]

    ops = [shift(o) for o in trace["devices"][dev]["ops"]
           if mods[0][1] <= o[1] < hi]
    host = [shift(h) for h in trace["host"] if t0 <= h[1] < hi]
    return {"devices": {"0": {"modules": [shift(m) for m in mods],
                              "ops": ops}}, "host": host}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gpt2_124m.steady")
    ap.add_argument("--seed", type=int, default=24)
    ap.add_argument("--out", required=True)
    ap.add_argument("--note", default="")
    args = ap.parse_args()
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    full = os.path.join(out, "trace_full.json")
    sys.argv = [run.__file__, "--workload", args.workload, "--seed",
                str(args.seed), "--trace", "1", "--dump-trace", full]
    rc = run.main()
    table = program.scope_table()
    if rc or table is None or not os.path.isfile(full):
        print("record: no trace or no scope table", file=sys.stderr)
        return rc or 1
    with open(full) as f:
        small = cut(json.load(f))
    os.unlink(full)
    names = {o[0] for o in small["devices"]["0"]["ops"]}
    small["_recorded"] = (
        f"{args.workload}, seed {args.seed}: the first two traced "
        f"optimizer steps; times in ns from 20 us before the first step. "
        f"{args.note}")
    stem = os.path.join(out, "steady_scoped_2steps")
    with gzip.open(stem + ".json.gz", "wt") as f:
        json.dump(small, f)
    with gzip.open(stem + ".scopes.json.gz", "wt") as f:
        json.dump({n: table.get(n, "") for n in sorted(names)}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
