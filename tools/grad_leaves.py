"""One step's gradient, leaf by leaf, between two checkouts.

    python tools/grad_leaves.py <cell> <seed> save <file.npz>      # in one
    python tools/grad_leaves.py <cell> <seed> compare <file.npz>   # in the other

Run from the root of a checkout (its own `benchmark/` and
`dlrover_wuqiong_tpu/` are imported): builds the cell's Trainer as
`benchmark/worker.py` does, draws the seeded state, runs ONE step of the
Trainer's own compiled program on the seed's first batch and reads
Adam's first moments of the new state (0.1 x the clipped gradient, leaf
by leaf, with no second program and no second copy of the gradient on
the device).  `save` writes them; `compare` prints one JSON line: how
many leaves differ from the saved set, the largest difference as a
share of a leaf's norm and of its largest entry, and the twelve leaves
furthest apart.  What `correct` compares is one loss and one norm
(PERF.md section 7); this is the comparison to ask for when a same-seed
pair's digits move (PR 50: both files of one call on the chip, the
parent unpacked by `git archive` into an ignored directory).
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.getcwd())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("seed", type=int)
    ap.add_argument("what", choices=("save", "compare"))
    ap.add_argument("path")
    ap.add_argument("--batch", type=int, default=0,
                    help="another global batch than the cell's (a CPU "
                         "rehearsal)")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(os.getcwd(), ".jax_cache"))
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("DWT_WARM_POOL", "0")
    import jax
    import numpy as np

    from benchmark import cells
    from benchmark.data import make_data
    from dlrover_wuqiong_tpu.trainer.trainer import Trainer, TrainingArgs

    cell = cells.load_cell(args.cell)
    if args.batch:
        cell["global_batch"] = args.batch
    cfg, traffic = cell["config"], cell["traffic"]
    model_mod = cells.load_module("models", cfg["model_class"])
    targs = dict(traffic["training_args"])
    targs.update(
        output_dir=tempfile.mkdtemp(prefix="grad_leaves_"),
        global_batch_size=cell["global_batch"], seq_len=cell["seq_len"],
        strategy=[(n, dict(o)) for n, o in cfg["train"]["strategy"]],
        seed=args.seed)
    data = make_data(cfg["vocab_size"], cell["global_batch"],
                     cell["seq_len"], args.seed, **traffic["data"])
    trainer = Trainer(model_mod.build(cfg), TrainingArgs(**targs), data)
    trainer.state = model_mod.seeded_state(trainer, args.seed)
    new, metrics = trainer.res.fused_train_step(1)(
        trainer.state, trainer.res.place_batch(data(0)))
    leaves = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            new.opt_state)[0]:
        keys = [str(getattr(k, "name", getattr(k, "key",
                                               getattr(k, "idx", k))))
                for k in path]
        if "mu" in keys and getattr(leaf, "ndim", 0):
            leaves["/".join(keys[keys.index("mu") + 1:])] = np.asarray(
                leaf, np.float32)
    trainer.ckpt.close()
    out = {"cell": args.cell, "seed": args.seed,
           "loss": float(metrics["loss"]),
           "grad_norm": float(metrics["grad_norm"]), "leaves": len(leaves)}
    if args.what == "save":
        os.makedirs(os.path.dirname(os.path.abspath(args.path)),
                    exist_ok=True)
        np.savez(args.path, **leaves)
    else:
        saved, rows = np.load(args.path), []
        for name, a in leaves.items():
            b = saved[name]
            rows.append({
                "norm_rel": float(np.linalg.norm((a - b).ravel())
                                  / max(np.linalg.norm(b.ravel()), 1e-30)),
                "peak_rel": float(np.abs(a - b).max()
                                  / max(np.abs(b).max(), 1e-30)),
                "differ": int((a != b).sum()), "size": int(a.size),
                "leaf": name})
        rows.sort(key=lambda r: -r["norm_rel"])
        out.update(leaves_that_differ=sum(1 for r in rows if r["differ"]),
                   largest_norm_rel=rows[0]["norm_rel"],
                   largest_peak_rel=max(r["peak_rel"] for r in rows),
                   top=rows[:12])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
