"""Model class `smallthinker`: how a SmallThinker-shaped configuration
file (the source's own HF keys) becomes the program's module —
`models/smallthinker.py`'s stack of windowed RoPE and global no-position
attention layers (`models/llama.py`'s attention, `ops/flash_attention.py`
with a window) and pre-attention-routed ReGLU expert layers
(`models/moe.py`) — its plain reference (`reference_smallthinker.py`),
and its operation counts.

The file's `moe_num_primary_experts` is how many experts are HELD (a
chip's share); the router's width is
`share.moe_num_primary_experts_published`.
"""

from __future__ import annotations

import functools

from benchmark import reference_smallthinker
from benchmark.models import gpt

seeded_state = gpt.seeded_state  # the draw every model class makes


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.smallthinker import (
        SmallThinker,
        SmallThinkerConfig,
    )

    if not config["model_name"].startswith("smallthinker"):
        raise ValueError("not a smallthinker configuration")
    if not config["moe_primary_router_apply_softmax"] \
            or not config["norm_topk_prob"]:
        raise ValueError("the program's gates are the softmax over the "
                         "chosen logits")
    if config["rope_scaling"] is not None:
        raise ValueError("the program's RoPE is unscaled")
    if config["tie_word_embeddings"]:
        raise ValueError("the program's head is untied")
    layouts = config["rope_layout"], config["sliding_window_layout"]
    if any(len(lay) != config["num_hidden_layers"] or set(lay) - {0, 1}
           for lay in layouts):
        raise ValueError("each layout has one 0 or 1 a layer")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("the query heads do not divide into the "
                         "key/value heads")
    if config["train"]["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("the sequence is longer than the positions")
    prog, share = config["program"], config["share"]
    if prog["impl"] != "grouped":
        raise ValueError("a share of the experts exists in the grouped "
                         "path only")
    return SmallThinker(SmallThinkerConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_layout=tuple(layouts[0]),
        sliding_window_layout=tuple(layouts[1]),
        sliding_window_size=config["sliding_window_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        num_experts=share["moe_num_primary_experts_published"],
        top_k=config["moe_num_active_primary_experts"],
        expert_width=config["moe_ffn_hidden_size"],
        experts_held=config["moe_num_primary_experts"],
        first_expert=share["first_expert"],
        aux_loss_weight=config["assumed"]["router_aux_loss_coef"],
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def reference_loss(config: dict, **over):
    """`loss(params, batch)` of the plain reference for this file;
    `over` replaces a size (the controls: a dtype, a layout)."""
    return functools.partial(
        reference_smallthinker.loss, **{**dict(
            rope_layout=tuple(config["rope_layout"]),
            sliding_window_layout=tuple(config["sliding_window_layout"]),
            window=config["sliding_window_size"],
            n_head=config["num_attention_heads"],
            n_kv_head=config["num_key_value_heads"],
            top_k=config["moe_num_active_primary_experts"],
            first_expert=config["share"]["first_expert"],
            eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
            aux_weight=config["assumed"]["router_aux_loss_coef"]), **over})


def kept_pairs(seq: int, window: int | None) -> int:
    """(query, key) pairs one head of one sequence keeps: the causal
    triangle seq*(seq+1)/2, or under a window that the sequence is
    longer than window*seq - window*(window-1)/2 (the first `window`
    queries see a growing prefix, every later one `window` keys)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * seq - window * (window - 1) // 2


def _layer_windows(config: dict) -> list:
    """Each layer's window, None for a global layer."""
    return [config["sliding_window_size"] if windowed else None
            for windowed in config["sliding_window_layout"]]


def _held_rows_per_token(config: dict) -> float:
    """Rows a token sends to the experts held here, EXPECTED under even
    routing: its choices fall on the held experts of the published
    count with that share (6 x 16 / 64 = 1.5 at the cell's)."""
    return config["moe_num_active_primary_experts"] \
        * config["moe_num_primary_experts"] \
        / config["share"]["moe_num_primary_experts_published"]


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through — the four attention projections, the
    router over all published experts, its ROUTED experts (three
    matrices each) at the EXPECTED rows a token sends to the experts
    held here (what a run really routes there is `moe.held_rows_share`),
    the untied head; the embedding is a lookup — plus attention's six
    matmuls on the KEPT pairs of each layer (`kept_pairs`: a windowed
    layer's band, never the causal triangle).  Norms, RoPE and gates are
    left out; recomputation is not counted."""
    h, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    seq = config["train"]["seq_len"]
    per_layer = 2 * h * q + 2 * h * kv \
        + h * config["share"]["moe_num_primary_experts_published"] \
        + _held_rows_per_token(config) * 3 * h * f
    params = config["num_hidden_layers"] * per_layer \
        + h * config["vocab_size"]
    pairs = sum(kept_pairs(seq, w) for w in _layer_windows(config))
    return 6.0 * params + 12.0 * q * pairs / seq


def _attention_cost(config: dict, global_batch: int, windows: list) -> dict:
    """`flops.causal_attention_cost`'s keys for the layers whose windows
    are `windows`, on KEPT pairs: six matmuls of 2*head_dim FLOPs a kept
    pair of every query head (forward 2, backward 4: a flash backward's
    recomputed scores are its own remat); of the bytes, k, v and their
    gradients once a KEY/VALUE head, q, o and theirs once a query head,
    as `nemotron_h.py` counts grouped heads."""
    seq, d = config["train"]["seq_len"], config["head_dim"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    pairs = sum(kept_pairs(seq, w) for w in windows)
    per_matmul = 2 * d * pairs * global_batch * heads
    tensors = len(windows) * global_batch * seq * d * 2 \
        * (heads + kv_heads)  # one q-shaped and one k-shaped tensor, bf16
    return {"flops_fwd": 2 * per_matmul, "flops_bwd": 4 * per_matmul,
            "flops": 6 * per_matmul, "bytes_fwd": 2 * tensors,
            "bytes_bwd": 4 * tensors, "bytes": 6 * tensors}


def attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """Attention FLOPs and bytes of one optimizer step over the whole
    batch, all layers, global and windowed, on kept pairs."""
    return _attention_cost(config, global_batch, _layer_windows(config))


def window_attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """The same for the WINDOWED layers alone: what
    `kernel.attn_window_roofline` holds against `dwt_fa_win_*`'s time."""
    return _attention_cost(config, global_batch, [
        w for w in _layer_windows(config) if w is not None])


def moe_cost_per_step(config: dict, global_batch: int,
                      bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the HELD experts' matmuls of one
    optimizer step, forward + backward, all layers, at the expected rows
    (`_held_rows_per_token`): what the ROUTING asks for.

    Each row passes three (hidden x width) matrices (ReGLU: gate, up,
    down): 2*hidden*width FLOPs each forward, twice that backward.  The
    router, the top-k, the sort and the row gathers are NOT in it.
    Bytes as `models/olmoe.py` counts them for a fused pass: forward
    reads the rows and the three weight tensors and writes the output
    rows; backward reads the rows, the output's gradient and the
    weights, and writes the rows' gradient and the three weight
    gradients."""
    h, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    rows = int(global_batch * config["train"]["seq_len"]
               * _held_rows_per_token(config))
    per_matmul = 2 * rows * h * f
    row_bytes = rows * h * bytes_per_el
    weight_bytes = 3 * config["moe_num_primary_experts"] * h * f \
        * bytes_per_el
    one = {"flops_fwd": 3 * per_matmul, "flops_bwd": 6 * per_matmul,
           "flops": 9 * per_matmul,
           "bytes_fwd": 2 * row_bytes + weight_bytes,
           "bytes_bwd": 3 * row_bytes + 2 * weight_bytes,
           "bytes": 5 * row_bytes + 3 * weight_bytes}
    return {k: v * config["num_hidden_layers"] for k, v in one.items()}
