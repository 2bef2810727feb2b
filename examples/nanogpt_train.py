"""Elastic GPT training — the nanoGPT example, TPU-native.

Parity: reference `examples/pytorch/nanogpt/train.py` (+ `fsdp_train.py`,
`elastic_job.yaml`): character-level GPT trained under the elastic agent
with flash checkpointing and automatic resume.

Run standalone:
    python examples/nanogpt_train.py --steps 50
Under the elastic CLI (crash-safe, auto-resume):
    python -m dlrover_wuqiong_tpu.run --standalone --nproc_per_node=1 \
        examples/nanogpt_train.py --steps 200
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def synthetic_char_batches(vocab, batch, seq, seed=0):
    """Stands in for nanogpt's shakespeare prepare.py on any machine."""
    rng = np.random.default_rng(seed)
    text = rng.integers(0, vocab, 1 << 16)
    while True:
        ix = rng.integers(0, len(text) - seq - 1, batch)
        x = np.stack([text[i:i + seq + 1] for i in ix])
        yield {"input_ids": x[:, :-1].astype(np.int32),
               "labels": x[:, 1:].astype(np.int32)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--output", default="/tmp/dwt-nanogpt")
    ap.add_argument("--gpt2", action="store_true",
                    help="full GPT-2 124M instead of the tiny config")
    args = ap.parse_args()

    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
    from dlrover_wuqiong_tpu.trainer.trainer import Trainer, TrainingArgs

    cfg = GPTConfig.gpt2() if args.gpt2 else GPTConfig.nano()
    targs = TrainingArgs(
        output_dir=args.output, max_steps=args.steps,
        global_batch_size=args.batch, seq_len=cfg.block_size,
        strategy=[("fsdp", {})], save_steps=20, logging_steps=10)
    data = synthetic_char_batches(cfg.vocab_size, args.batch,
                                  cfg.block_size)
    out = Trainer(GPT(cfg), targs, data).train()
    print("final:", out)


if __name__ == "__main__":
    main()
