"""Model class `laguna`: how a Laguna-shaped configuration file (the
source's own HF keys) becomes the program's module —
`models/laguna.py`'s stack of full and sliding-window attention layers
with a head count of their own each, a gate a head on their output, a
part of the head rotated under YaRN (`models/llama.py`'s attention,
`ops/flash_attention.py` with and without a window), a leading dense
SwiGLU and softmax-routed SwiGLU expert layers with a shared expert
(`models/moe.py`) — its plain reference (`reference_laguna.py`), and its
operation and byte counts.

The file's `num_experts` is how many experts are HELD (a chip's share);
the router's width is `share.num_experts_published`.

What an architecture whose HEAD COUNT CHANGES WITH THE LAYER has to
supply here, beside the five functions every class has (`build`,
`seeded_state`, `reference_loss`, `train_flops_per_token`,
`attention_cost_per_step`; benchmark/README.md, "Adding things"): every
count is a SUM OVER THE LAYERS at the layer's own heads
(`num_attention_heads_per_layer[l]`) and the layer's own kept pairs
(`layer_types[l]`: the causal triangle or the window's band), never one
layer's cost times the depth: `attention_cost_per_step` is what
`kernel.attn_roofline` divides by, `window_attention_cost_per_step`
(the sliding layers alone) what `kernel.attn_window_roofline` does,
`train_flops_per_token` what `device.mfu_pct` does, and a count at 64
heads for a 48-head layer would read each a third too high there.  A
class with an expert layer adds `moe_cost_per_step`, and a scopes file
(`laguna.scopes.json`) with the parts every class names plus what is its
own (`gate_parts`: the output gate).
"""

from __future__ import annotations

import functools
import math

from benchmark import reference_laguna
from benchmark.models import gpt, smallthinker

FULL, SLIDING = "full_attention", "sliding_attention"

# the draw every model class makes: the gate's product is drawn as every
# dense kernel is (lecun-normal over 2,048 inputs of unit RMS), so a
# gate's logit has unit variance and the gates lie over 0.27-0.73 and
# beyond from the first step: no leaf needs a draw of its own
seeded_state = gpt.seeded_state
kept_pairs = smallthinker.kept_pairs  # the triangle, or a window's band


def _rope(config: dict, kind: str) -> dict:
    return config["rope_parameters"][kind]


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.laguna import Laguna, LagunaConfig
    from dlrover_wuqiong_tpu.models.llama import RopeScaling

    if config["model_type"] != "laguna":
        raise ValueError("not a laguna configuration")
    depth = config["num_hidden_layers"]
    lists = (config["layer_types"], config["mlp_layer_types"],
             config["num_attention_heads_per_layer"])
    if any(len(per_layer) != depth for per_layer in lists):
        raise ValueError("layer_types, mlp_layer_types and "
                         "num_attention_heads_per_layer have one entry a "
                         "layer")
    if set(lists[0]) - {FULL, SLIDING} or set(lists[1]) - {"dense", "sparse"}:
        raise ValueError("a layer's attention is full or sliding, its "
                         "feed-forward dense or sparse")
    if any(h % config["num_key_value_heads"] for h in lists[2]):
        raise ValueError("a layer's query heads do not divide into the "
                         "key/value heads")
    if config["gating"] is not True:
        raise ValueError("the program's gate is one sigmoid a head")
    if config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("the program's projections have no bias and its "
                         "head is untied")
    if config["moe_apply_router_weight_on_input"]:
        raise ValueError("the program weighs the experts' OUTPUT")
    full, sliding = _rope(config, FULL), _rope(config, SLIDING)
    if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default"):
        raise ValueError("the program rotates full layers under YaRN and "
                         "sliding ones unscaled")
    if full["partial_rotary_factor"] != config["partial_rotary_factor"]:
        raise ValueError("two partial_rotary_factors for the full layers")
    if not math.isclose(full["attention_factor"],
                        0.1 * math.log(full["factor"]) + 1.0, rel_tol=1e-12):
        raise ValueError("the program's tables carry 0.1 ln(factor) + 1")
    if config["train"]["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("the sequence is longer than the positions")
    prog, share = config["program"], config["share"]
    if prog["impl"] != "grouped":
        raise ValueError("a share of the experts exists in the grouped "
                         "path only")
    return Laguna(LagunaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=tuple(lists[0]), mlp_layer_types=tuple(lists[1]),
        num_heads_per_layer=tuple(lists[2]),
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"], attn_gate=True,
        max_seq_len=config["max_position_embeddings"],
        full_rope_theta=float(full["rope_theta"]),
        full_rope_scaling=RopeScaling(
            factor=float(full["factor"]),
            original_max_position_embeddings=full[
                "original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"])),
        full_rotary_factor=full["partial_rotary_factor"],
        sliding_rope_theta=float(sliding["rope_theta"]),
        sliding_rotary_factor=sliding["partial_rotary_factor"],
        rms_eps=config["rms_norm_eps"],
        dense_width=config["intermediate_size"],
        num_experts=share["num_experts_published"],
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["shared_expert_intermediate_size"],
        routed_scaling=config["moe_routed_scaling_factor"],
        experts_held=config["num_experts"],
        first_expert=share["first_expert"],
        aux_loss_weight=config["assumed"]["router_aux_loss_coef"],
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def reference_loss(config: dict, **over):
    """`loss(params, batch)` of the plain reference for this file;
    `over` replaces a size or sets a control (`wrong=("gate",)`, a
    dtype)."""
    return functools.partial(
        reference_laguna.loss, **{**dict(
            layer_types=tuple(config["layer_types"]),
            mlp_layer_types=tuple(config["mlp_layer_types"]),
            rope_parameters=config["rope_parameters"],
            window=config["sliding_window"],
            n_kv_head=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            top_k=config["num_experts_per_tok"],
            routed_scaling=config["moe_routed_scaling_factor"],
            first_expert=config["share"]["first_expert"],
            eps=config["rms_norm_eps"],
            aux_weight=config["assumed"]["router_aux_loss_coef"]), **over})


def _attention_layers(config: dict, kinds=(FULL, SLIDING)) -> list:
    """(query heads, kept pairs a head and sequence) of each layer whose
    kind is among `kinds`: the layer's OWN head count beside its own
    mask."""
    seq = config["train"]["seq_len"]
    return [(heads, kept_pairs(
        seq, config["sliding_window"] if kind == SLIDING else None))
        for kind, heads in zip(config["layer_types"],
                               config["num_attention_heads_per_layer"])
        if kind in kinds]


def _sparse_layers(config: dict) -> int:
    return sum(kind == "sparse" for kind in config["mlp_layer_types"])


def _held_rows_per_token(config: dict) -> float:
    """Rows a token sends to the experts held here, EXPECTED under even
    routing: its choices fall on the held experts of the published count
    with that share (8 x 32 / 256 = 1 at the cell's)."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["share"]["num_experts_published"]


def dense_params_per_token(config: dict) -> dict:
    """Matmul parameters (multiply-adds) one token passes through, by
    part: each layer's four projections and its gate's product at that
    layer's heads, the dense SwiGLUs, the routers over all published
    experts, the ROUTED experts (three matrices each) at the expected
    rows a token sends to the experts held here (what a run really
    routes there is `moe.held_rows_share`) and the shared expert, the
    untied head; the embedding is a lookup."""
    h, d = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"] * d
    sparse = _sparse_layers(config)
    return {
        "attention": sum(2 * h * heads * d + 2 * h * kv + h * heads
                         for heads in config["num_attention_heads_per_layer"]),
        "dense": (config["num_hidden_layers"] - sparse) * 3 * h
        * config["intermediate_size"],
        "router": sparse * h * config["share"]["num_experts_published"],
        "experts": sparse * 3 * h * (
            _held_rows_per_token(config) * config["moe_intermediate_size"]
            + config["shared_expert_intermediate_size"]),
        "head": h * config["vocab_size"]}


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through (`dense_params_per_token`) plus
    attention's six matmuls on the KEPT pairs of each layer at that
    layer's heads (a sliding layer's band, never the causal triangle).
    Norms, RoPE, the gate's sigmoid and multiply are left out;
    recomputation is not counted."""
    seq, d = config["train"]["seq_len"], config["head_dim"]
    pairs = sum(heads * kept for heads, kept in _attention_layers(config))
    return 6.0 * sum(dense_params_per_token(config).values()) \
        + 12.0 * d * pairs / seq


def _attention_cost(config: dict, global_batch: int, layers: list) -> dict:
    """`flops.causal_attention_cost`'s keys for `layers` ((heads, kept
    pairs) each): six matmuls of 2*head_dim FLOPs a kept pair of every
    query head of THAT layer (forward 2, backward 4: a flash backward's
    recomputed scores are its own remat); of the bytes, k, v and their
    gradients once a KEY/VALUE head, q, o and theirs once a query head
    of that layer, as `smallthinker.py` counts grouped heads."""
    seq, d = config["train"]["seq_len"], config["head_dim"]
    per_matmul = 2 * d * global_batch * sum(
        heads * kept for heads, kept in layers)
    tensors = global_batch * seq * d * 2 * sum(  # a q- and a k-shaped, bf16
        heads + config["num_key_value_heads"] for heads, _ in layers)
    return {"flops_fwd": 2 * per_matmul, "flops_bwd": 4 * per_matmul,
            "flops": 6 * per_matmul, "bytes_fwd": 2 * tensors,
            "bytes_bwd": 4 * tensors, "bytes": 6 * tensors}


def attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """Attention FLOPs and bytes of one optimizer step over the whole
    batch, all layers, full and sliding, each at its own heads and its
    own kept pairs."""
    return _attention_cost(config, global_batch, _attention_layers(config))


def window_attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """The same for the SLIDING layers alone: what
    `kernel.attn_window_roofline` holds against `dwt_fa_win_*`'s time."""
    return _attention_cost(config, global_batch,
                           _attention_layers(config, (SLIDING,)))


def moe_cost_per_step(config: dict, global_batch: int,
                      bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the HELD experts' matmuls of one
    optimizer step, forward + backward, the sparse layers, at the
    expected rows (`_held_rows_per_token`): what the ROUTING asks for.

    Each row passes three (hidden x width) matrices (SwiGLU: gate, up,
    down): 2*hidden*width FLOPs each forward, twice that backward.  The
    router, the top-k, the sort, the row gathers and the shared expert
    (three dense products, not grouped ones) are NOT in it.  Bytes as
    `smallthinker.py` counts them for a fused pass: forward reads the
    rows and the three weight tensors and writes the output rows;
    backward reads the rows, the output's gradient and the weights, and
    writes the rows' gradient and the three weight gradients."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    rows = int(global_batch * config["train"]["seq_len"]
               * _held_rows_per_token(config))
    per_matmul = 2 * rows * h * f
    row_bytes = rows * h * bytes_per_el
    weight_bytes = 3 * config["num_experts"] * h * f * bytes_per_el
    one = {"flops_fwd": 3 * per_matmul, "flops_bwd": 6 * per_matmul,
           "flops": 9 * per_matmul,
           "bytes_fwd": 2 * row_bytes + weight_bytes,
           "bytes_bwd": 3 * row_bytes + 2 * weight_bytes,
           "bytes": 5 * row_bytes + 3 * weight_bytes}
    return {k: v * _sparse_layers(config) for k, v in one.items()}
