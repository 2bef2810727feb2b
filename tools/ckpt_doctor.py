#!/usr/bin/env python
"""ckpt_doctor — offline checkpoint verification / repair CLI.

    python tools/ckpt_doctor.py /path/to/ckpt_dir            # verify
    python tools/ckpt_doctor.py /path/to/ckpt_dir --repair   # + quarantine
    python tools/ckpt_doctor.py gs://bucket/run1 --step 400  # one generation

Walks every generation under a checkpoint dir (posix or object store),
verifies each against its committed manifest (checkpoint/integrity.py:
manifest presence, per-rank meta digests, shard-file digests, and with
--deep per-leaf digests to pinpoint WHICH tensor a corruption hit), and
prints ONE JSON line on stdout (machine-readable for
CI and cron'd health checks on real TPU runs); human detail goes to
stderr.  `--repair` moves failing generations to the `.quarantine/`
sidecar — never deletes — and repoints the tracker at the newest
generation that still verifies, exactly what the engine's restore chain
would do lazily.  Exit code: 0 all healthy, 1 any corruption found.

No jax import, no backend touch: safe to run next to a live job.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="ckpt_doctor", description="verify/repair a checkpoint dir")
    p.add_argument("path", help="checkpoint dir (posix or gs://...)")
    p.add_argument("--step", type=int, default=None,
                   help="verify one generation only")
    p.add_argument("--repair", action="store_true",
                   help="quarantine corrupt generations + fix the tracker")
    p.add_argument("--deep", action="store_true",
                   help="per-leaf digests (pinpoints the corrupt tensor)")
    args = p.parse_args(argv)

    from dlrover_wuqiong_tpu.checkpoint.ckpt_saver import read_last_step
    from dlrover_wuqiong_tpu.checkpoint.integrity import (
        list_quarantined,
        quarantine_step,
        verify_storage_step,
    )
    from dlrover_wuqiong_tpu.common.constants import CheckpointConstant
    from dlrover_wuqiong_tpu.common.storage import get_checkpoint_storage

    storage = get_checkpoint_storage(path_hint=args.path)
    prefix = CheckpointConstant.CKPT_NAME_PREFIX
    steps = []
    for name in storage.listdir(args.path):
        if name.startswith(prefix):
            try:
                steps.append(int(name[len(prefix):]))
            except ValueError:
                continue
    if args.step is not None:
        steps = [s for s in steps if s == args.step]
    steps.sort(reverse=True)

    tracker = read_last_step(args.path, storage)
    gens, quarantined = [], []
    for s in steps:
        v = verify_storage_step(storage, args.path, s, per_leaf=args.deep)
        row = {"step": s, "ok": v["ok"], "reason": v["reason"],
               "ranks": v["ranks"]}
        if v["bad_leaves"]:
            row["bad_leaves"] = v["bad_leaves"]
        gens.append(row)
        if not v["ok"]:
            print(f"step {s}: CORRUPT ({v['reason']})"
                  + (f" leaves={v['bad_leaves']}" if v["bad_leaves"]
                     else ""), file=sys.stderr)
            if args.repair:
                qdir = quarantine_step(storage, args.path, s,
                                       f"doctor: {v['reason']}")
                row["quarantined"] = qdir
                quarantined.append(s)
        else:
            print(f"step {s}: ok ({v['ranks']} rank(s))", file=sys.stderr)

    healthy = [g["step"] for g in gens if g["ok"]]
    if args.repair and tracker >= 0 and tracker not in healthy:
        new_tracker = max(healthy) if healthy else -1
        if new_tracker >= 0:
            storage.write(str(new_tracker), os.path.join(
                args.path, CheckpointConstant.TRACKER_FILE))
            print(f"tracker repointed {tracker} -> {new_tracker}",
                  file=sys.stderr)
        tracker = new_tracker

    verdict = {
        "ckpt_doctor": {
            "path": args.path,
            "tracker_step": tracker,
            "generations": gens,
            "healthy_steps": healthy,
            "quarantined_now": quarantined,
            "quarantine_dir_entries": len(
                list_quarantined(storage, args.path)),
            "ok": all(g["ok"] for g in gens) if gens else False,
        }
    }
    print(json.dumps(verdict))
    return 0 if verdict["ckpt_doctor"]["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
