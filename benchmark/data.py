"""Seeded training data — the benchmark's own generator.

One general generator; a traffic file's `data` group holds its
parameters.  Copied from `chip_smoke.make_data` (PR 22) so the yardstick
does not move when the program does: windows of one fixed random text
over a small alphabet, a pure function of (seed, step), so a resumed
worker sees the batches the dead one would have, and learnable, so the
loss falls within a few steps.
"""

from __future__ import annotations

import numpy as np


def make_data(vocab: int, batch: int, seq: int, seed: int,
              alphabet: int = 256, text_tokens: int = 1 << 16):
    """`(step) -> {"input_ids", "labels"}`, int32, shape (batch, seq)."""
    text = np.random.default_rng(seed).integers(
        0, min(alphabet, vocab), text_tokens).astype(np.int32)

    def batch_at(step: int):
        ix = np.random.default_rng((seed, step)).integers(
            0, len(text) - seq - 1, batch)
        x = np.stack([text[i:i + seq + 1] for i in ix])
        return {"input_ids": x[:, :-1], "labels": x[:, 1:]}

    return batch_at
