"""Model class `olmoe`: how an OLMoE-shaped configuration file (the
source's own HF keys) becomes the program's module — `models/llama.py`'s
block with `models/moe.py`'s expert layer and QK-norm — its plain
reference (`reference_olmoe.py`), and its operation counts.
"""

from __future__ import annotations

import functools

from benchmark import flops, reference_olmoe
from benchmark.models.gpt import seeded_state  # noqa: F401 — it draws any
# model's state through `model.init_params`; nothing in it is GPT's


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.llama import Llama, LlamaConfig
    from dlrover_wuqiong_tpu.models.moe import MoEConfig

    if config["model_type"] != "olmoe" or config["hidden_act"] != "silu":
        raise ValueError("the program's expert is SwiGLU (silu) only")
    if config["attention_bias"] or config["clip_qkv"] is not None:
        raise ValueError("the program's attention has no bias and clips "
                         "nothing")
    if config["rope_scaling"] is not None:
        raise ValueError("the program's RoPE is unscaled")
    if config["tie_word_embeddings"]:
        raise ValueError("the program's Llama has an untied head")
    if config["hidden_size"] % config["num_attention_heads"]:
        raise ValueError("heads do not divide the hidden size")
    if config["train"]["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("the sequence is longer than the positions")
    prog = config["program"]
    dtype = getattr(jnp, prog["dtype"])
    moe = MoEConfig(
        num_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        aux_loss_weight=config["router_aux_loss_coef"],
        z_loss_weight=config["assumed"]["router_z_loss_coef"],
        dtype=dtype, impl=prog["impl"],
        norm_topk_prob=config["norm_topk_prob"], aux_loss="topk")
    return Llama(LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"], dtype=dtype, remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"], moe=moe,
        qk_norm=True))


def reference_loss(config: dict):
    """`loss(params, batch)` of the plain reference for this file."""
    return functools.partial(
        reference_olmoe.loss, n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
        aux_weight=config["router_aux_loss_coef"],
        z_weight=config["assumed"]["router_z_loss_coef"])


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through — the four attention projections, the
    router, its `num_experts_per_tok` experts (three matrices each), the
    untied head; the embedding is a lookup — plus causal attention's
    6*T*hidden a layer.  Recomputation is not counted."""
    h, f = config["hidden_size"], config["intermediate_size"]
    kv = h // config["num_attention_heads"] * config["num_key_value_heads"]
    per_layer = 2 * h * h + 2 * h * kv + h * config["num_experts"] \
        + config["num_experts_per_tok"] * 3 * h * f
    n = config["num_hidden_layers"] * per_layer + h * config["vocab_size"]
    return 6.0 * n + 6.0 * config["num_hidden_layers"] \
        * config["train"]["seq_len"] * h


def attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """Causal attention FLOPs and bytes of one optimizer step over the
    whole batch, all layers (divide by chips for one chip's share)."""
    one = flops.causal_attention_cost(
        global_batch, config["num_attention_heads"],
        config["train"]["seq_len"],
        config["hidden_size"] // config["num_attention_heads"],
        bytes_per_el=2)
    return {k: v * config["num_hidden_layers"] for k, v in one.items()}


def moe_cost_per_step(config: dict, global_batch: int,
                      bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the experts' grouped matmuls of one
    optimizer step, forward + backward, all layers.

    Rows = tokens x experts per token; each row passes three (hidden x
    width) matrices: 2*hidden*width FLOPs each forward, twice that
    backward (the row's gradient and the weight's).  The router, the
    top-k, the sort, the gather and the scatter-add are NOT in it: they
    are routing, not grouped matmuls.
    Bytes, as a fused expert pass needs them (gate/up/hidden rows never
    leave the chip's fast memory): forward reads the sorted rows and the
    three weight tensors and writes the output rows; backward reads the
    rows, the output's gradient and the weights, and writes the rows'
    gradient and the three weight gradients."""
    h, f = config["hidden_size"], config["intermediate_size"]
    rows = global_batch * config["train"]["seq_len"] \
        * config["num_experts_per_tok"]
    per_matmul = 2 * rows * h * f
    row_bytes = rows * h * bytes_per_el
    weight_bytes = 3 * config["num_experts"] * h * f * bytes_per_el
    one = {"flops_fwd": 3 * per_matmul, "flops_bwd": 6 * per_matmul,
           "flops": 9 * per_matmul,
           "bytes_fwd": 2 * row_bytes + weight_bytes,
           "bytes_bwd": 3 * row_bytes + 2 * weight_bytes,
           "bytes": 5 * row_bytes + 3 * weight_bytes}
    return {k: v * config["num_hidden_layers"] for k, v in one.items()}
