"""Operations and bytes from shapes, and the table of peaks.

Everything here is arithmetic on a configuration's sizes; nothing is
measured.  The benchmark owns it so that no PR that claims a gain can
move it.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"{_PEAKS}; add a row with its source")
    return table[device_kind]


def causal_attention_cost(batch: int, heads: int, seq: int, head_dim: int,
                          bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of ONE causal attention layer, forward
    plus backward, as the algorithm needs them (no recomputation counted).

    A causal mask leaves seq*(seq+1)/2 of the seq*seq score entries.
    Forward: S = QK^T and O = PV, 2 matmuls.  Backward: dV = P^T dO,
    dP = dO V^T, dQ = dS K, dK = dS^T Q, 4 matmuls.  Each is 2*head_dim
    FLOPs per kept score entry.  A flash backward also recomputes S; that
    is the kernel's own remat and is NOT counted as useful work.
    Bytes: forward reads Q, K, V and writes O (4 tensors); backward reads
    Q, K, V, O, dO and writes dQ, dK, dV (8 tensors); the softmax
    statistics (one float per row) are left out as negligible.
    """
    kept = seq * (seq + 1) // 2
    per_matmul = 2 * head_dim * kept * batch * heads
    tensor = batch * heads * seq * head_dim * bytes_per_el
    return {"flops_fwd": 2 * per_matmul, "flops_bwd": 4 * per_matmul,
            "flops": 6 * per_matmul,
            "bytes_fwd": 4 * tensor, "bytes_bwd": 8 * tensor,
            "bytes": 12 * tensor}


def roofline(flops: float, nbytes: float, peak: dict) -> dict:
    """Least seconds the chip could take, and which peak bounds it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}


def gpt_params(vocab: int, n_pos: int, n_layer: int, n_embd: int) -> int:
    """Parameters of a GPT-2-shaped model with a tied output head."""
    per_layer = 12 * n_embd * n_embd + 13 * n_embd
    return (vocab + n_pos) * n_embd + n_layer * per_layer + 2 * n_embd


def gpt_train_flops_per_token(vocab: int, n_pos: int, n_layer: int,
                              n_embd: int, seq: int) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter (the position table does no matmul; the tied head is
    counted once, through `wte`) plus causal attention's 6*L*T*C (the
    12*L*T*C of full attention, halved by the mask).  Recomputation is
    not counted."""
    n = gpt_params(vocab, n_pos, n_layer, n_embd) - n_pos * n_embd
    return 6.0 * n + 6.0 * n_layer * seq * n_embd
