"""Operation counts against hand counts; the table of peaks."""

import json
import os

import pytest

from benchmark import cells, flops


def test_causal_attention_cost_hand_count():
    # 1 sequence, 1 head, T=4, d=2, bf16: 4*5/2 = 10 kept score entries.
    # One matmul over them: 2*d*10 = 40 FLOPs.  Forward 2 matmuls (QK^T,
    # PV) = 80, backward 4 (dV, dP, dQ, dK) = 160.  One tensor is
    # 1*1*4*2 elements * 2 B = 16 B; forward moves 4 (Q, K, V, O) = 64 B,
    # backward 8 (Q, K, V, O, dO, dQ, dK, dV) = 128 B.
    c = flops.causal_attention_cost(1, 1, 4, 2, bytes_per_el=2)
    assert c == {"flops_fwd": 80, "flops_bwd": 160, "flops": 240,
                 "bytes_fwd": 64, "bytes_bwd": 128, "bytes": 192}
    # linear in batch and heads
    c2 = flops.causal_attention_cost(3, 5, 4, 2, bytes_per_el=2)
    assert c2["flops"] == 15 * 240 and c2["bytes"] == 15 * 192


def test_gpt2_counts():
    # 124,475,904 parameters (PR 22's log line); 0.80 and 9.8 GFLOP/token
    assert flops.gpt_params(50304, 1024, 12, 768) == 124_475_904
    assert flops.gpt_params(50304, 1024, 48, 1600) == 1_557_686_400
    small = flops.gpt_train_flops_per_token(50304, 1024, 12, 768, 1024)
    assert small == 6 * (124_475_904 - 1024 * 768) + 6 * 12 * 1024 * 768
    assert round(small / 1e9, 2) == 0.80
    xl = flops.gpt_train_flops_per_token(50304, 1024, 48, 1600, 1024)
    assert round(xl / 1e9, 1) == 9.8


def test_model_file_agrees_with_flops():
    cell = cells.load_cell("gpt2_124m.steady")
    model = cells.load_module("models", "gpt")
    assert round(model.train_flops_per_token(cell["config"]) / 1e9, 2) == 0.80
    cost = model.attention_cost_per_step(cell["config"], 24)
    one = flops.causal_attention_cost(24, 12, 1024, 64)
    assert cost["flops"] == 12 * one["flops"]
    # compute-bound at d=64, T=1024 in bf16: 7.1 ms against 6.6 ms
    r = flops.roofline(cost["flops"], cost["bytes"],
                       flops.peaks("TPU v5 lite"))
    assert r["bound"] == "compute" and 7.0e-3 < r["seconds"] < 7.2e-3


def test_roofline_says_which_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline(200.0, 10.0, peak) == {"seconds": 2.0,
                                                 "bound": "compute"}
    assert flops.roofline(100.0, 50.0, peak) == {"seconds": 5.0,
                                                 "bound": "memory"}


def test_peaks_known_and_unknown():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", "_source", ""):
        with pytest.raises(KeyError):
            flops.peaks(kind)
    with open(os.path.join(cells.HERE, "peaks.json")) as f:
        table = json.load(f)
    assert "cpu" not in table and "source" in table["_source"].lower() \
        or "documentation" in table["_source"]
