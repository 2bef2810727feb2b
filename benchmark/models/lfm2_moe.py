"""Model class `lfm2_moe`: how an LFM2-24B-A2B-shaped configuration file
(the source's own HF keys) becomes the program's module —
`models/lfm2.py`'s stack of gated short-convolution mixers beside
grouped-query attention with a per-head QK norm (`models/llama.py`), a
leading dense SwiGLU layer and sigmoid-routed SwiGLU expert layers
without a shared expert (`models/moe.py`), under a tied head — its plain
reference (`reference_lfm2_moe.py`), and its operation counts.

The file's `num_experts` is how many experts are HELD (a chip's share);
the router's width is `share.num_experts_published`.
"""

from __future__ import annotations

import functools

from benchmark import flops, reference_lfm2_moe
from benchmark.models import kimi_vl

# q's and k's projections at the seeded state, times the lecun draw
SEEDED_QK_SCALE = 2.0


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.lfm2 import Lfm2, Lfm2Config

    if config["model_type"] != "lfm2_moe":
        raise ValueError("not an lfm2_moe configuration")
    if not config["use_expert_bias"] or not config["norm_topk_prob"]:
        raise ValueError("the program's router chooses under an expert "
                         "bias and normalises the chosen gates")
    if config["conv_bias"]:
        raise ValueError("the program's short convolution has no bias")
    if config["rope_parameters"]["rope_type"] != "default":
        raise ValueError("the program's RoPE is unscaled")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types has one entry a layer")
    if not 0 < config["num_dense_layers"] <= config["num_hidden_layers"]:
        raise ValueError("the leading dense layers lie inside the depth")
    if config["hidden_size"] % config["num_attention_heads"] \
            or config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("heads divide the hidden size, kv heads the heads")
    if config["train"]["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("the sequence is longer than the positions")
    prog, share = config["program"], config["share"]
    if prog["impl"] != "grouped":
        raise ValueError("a share of the experts exists in the grouped "
                         "path only")
    return Lfm2(Lfm2Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["num_dense_layers"],
        dense_width=config["intermediate_size"],
        conv_taps=config["conv_L_cache"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        rms_eps=config["norm_eps"],
        num_experts=share["num_experts_published"],
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        routed_scaling=float(config["routed_scaling_factor"]),
        gate_norm_eps=prog["gate_norm_eps"],
        experts_held=config["num_experts"],
        first_expert=share["first_expert"],
        bias_update_rate=config["train"]["selection_bias_update_rate"],
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def seeded_leaves(params):
    """`params` with every attention layer's q and k projections times
    `SEEDED_QK_SCALE`: at the lecun draw a head of q or k has an RMS of
    1, which is what its norm would leave alone, and the two scalars the
    check compares could not see a missing per-head norm (my chip run,
    PR 60: 3.4e-5 on the loss and 1.8e-4 on the gradient norm, inside any
    limit).  The NORMED model computes what it computed: an RMSNorm
    takes a scale out again."""
    def scaled(layer):
        if "attention" not in layer:
            return layer
        attention = dict(layer["attention"])
        for name in ("q_proj", "k_proj"):
            attention[name] = {"kernel": attention[name]["kernel"]
                               * SEEDED_QK_SCALE}
        return {**layer, "attention": attention}

    return {name: scaled(sub) if name.startswith("layers_") else sub
            for name, sub in params.items()}


def seeded_state(trainer, seed: int):
    """`kimi_vl.seeded_state` — every leaf from `seed`, each expert
    layer's selection bias set to where the out-of-band rule settles on
    the seed's first batch — over a draw that ends in `seeded_leaves`:
    the one jitted draw that `gpt.seeded_state` keeps on the trainer is
    made here, before it looks for one (as `xing4_0.seeded_state`)."""
    import jax

    from dlrover_wuqiong_tpu.trainer.train_step import TrainState

    if getattr(trainer, "_bench_seeded_init", None) is None:
        model, optimizer = trainer.res.model, trainer.optimizer
        trainer._bench_seeded_init = jax.jit(
            lambda key: TrainState.create(
                seeded_leaves(model.init_params(key)), optimizer),
            out_shardings=trainer.res.state_shardings)
    return kimi_vl.seeded_state(trainer, seed)


def reference_loss(config: dict, **over):
    """`loss(params, batch)` of the plain reference for this file;
    `over` replaces a size or sets a control (`dtype=`, `wrong=`)."""
    return functools.partial(
        reference_lfm2_moe.loss, **{**dict(
            layer_types=tuple(config["layer_types"]),
            n_dense=config["num_dense_layers"],
            n_head=config["num_attention_heads"],
            n_kv=config["num_key_value_heads"],
            theta=float(config["rope_parameters"]["rope_theta"]),
            top_k=config["num_experts_per_tok"],
            routed_scaling=float(config["routed_scaling_factor"]),
            first_expert=config["share"]["first_expert"],
            eps=config["norm_eps"]), **over})


def _counts(config: dict) -> tuple:
    """(conv layers, attention layers, leading dense layers, expert
    layers)."""
    kinds, dense = config["layer_types"], config["num_dense_layers"]
    conv = sum(kind == "conv" for kind in kinds)
    return conv, len(kinds) - conv, dense, len(kinds) - dense


def _held_rows_per_token(config: dict) -> float:
    """Rows a token sends to the experts held here, EXPECTED under even
    routing (4 x 8 / 64 = 0.5 at the cell's)."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["share"]["num_experts_published"]


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def dense_params_per_token(config: dict) -> dict:
    """Matmul parameters one token passes through, by part: a conv
    mixer's two products (hidden x 3 hidden in, hidden x hidden out), the
    attention's four (k and v at the kv heads), the leading dense SwiGLU,
    the expert layers (router over all published experts, ROUTED experts
    at the expected rows a token sends to the experts held here), the
    tied head; the embedding is a lookup."""
    h = config["hidden_size"]
    kv = config["num_key_value_heads"] * _head_dim(config)
    n_conv, n_attn, dense, expert = _counts(config)
    f = config["moe_intermediate_size"]
    return {
        "conv": n_conv * 4 * h * h,
        "attention": n_attn * (2 * h * h + 2 * h * kv),
        "dense": dense * 3 * h * config["intermediate_size"],
        "router": expert * h * config["share"]["num_experts_published"],
        "routed": expert * _held_rows_per_token(config) * 3 * h * f,
        "head": h * config["vocab_size"]}


def attention_pairs_flops_per_token(config: dict) -> float:
    """FORWARD FLOPs a token of the attention kernels' two products, the
    attention layers: 2 x (64 + 64) a kept pair of every query head."""
    seq = config["train"]["seq_len"]
    return 4.0 * config["hidden_size"] * _counts(config)[1] * (seq + 1) / 2


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through (`dense_params_per_token`) plus three
    times the attention products' forward FLOPs.  The gates, the filter's
    three taps, norms and RoPE are left out; recomputation is not
    counted."""
    return 6.0 * sum(dense_params_per_token(config).values()) \
        + 3.0 * attention_pairs_flops_per_token(config)


def attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """Causal attention FLOPs and bytes of one optimizer step over the
    whole batch, the attention layers, as `nemotron_h.py` counts grouped
    heads: FLOPs of every query head; of the bytes, k, v and their
    gradients once a KEY/VALUE head, q, o and theirs once a query head."""
    seq, d = config["train"]["seq_len"], _head_dim(config)
    per_q = flops.causal_attention_cost(
        global_batch, config["num_attention_heads"], seq, d, bytes_per_el=2)
    per_kv = flops.causal_attention_cost(
        global_batch, config["num_key_value_heads"], seq, d, bytes_per_el=2)
    one = {k: v for k, v in per_q.items() if k.startswith("flops")}
    for k in ("bytes_fwd", "bytes_bwd", "bytes"):
        one[k] = (per_q[k] + per_kv[k]) // 2  # half the tensors are k, v
    return {k: v * _counts(config)[1] for k, v in one.items()}


def shortconv_cost_per_step(config: dict, global_batch: int,
                            bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the gated short-convolution MIXERS of
    one optimizer step, all conv layers, from the configuration and the
    batch alone — the WHOLE mixer, so that no fusion of a gate into a
    product moves work out of what `shortconv.roofline` divides by, and
    the same work whatever later implements it.

    FLOPs: the mixer's two products, hidden x 3 hidden and hidden x
    hidden: 8 x hidden^2 a token forward; the two gates and the three
    taps (11 x hidden a token) are not counted.  Bytes: a phase reads the
    two matrices and the filter once, reads the normalised input h and
    writes the mixer's output y, once each, at `bytes_per_el`; everything
    between the two products could stay on the chip.  Both are forward +
    the recomputed forward (where `program.remat`) + a backward at twice
    the forward.  Both err low: the share of the roofline this gives
    cannot pass 100% by a later change of form."""
    h = config["hidden_size"]
    tokens = global_batch * config["train"]["seq_len"]
    phases = 4 if config["program"]["remat"] else 3
    fwd_flops = 8 * tokens * h * h
    fwd_bytes = (4 * h * h + config["conv_L_cache"] * h + 2 * tokens * h) \
        * bytes_per_el
    n_conv = _counts(config)[0]
    return {"flops_fwd": n_conv * fwd_flops, "bytes_fwd": n_conv * fwd_bytes,
            "flops": n_conv * phases * fwd_flops,
            "bytes": n_conv * phases * fwd_bytes}


def moe_cost_per_step(config: dict, global_batch: int,
                      bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the HELD experts' matmuls of one
    optimizer step, forward + backward, all expert layers, at the
    expected rows (`_held_rows_per_token`), as
    `kimi_vl.moe_cost_per_step` counts them."""
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
    return {k: v * _counts(config)[3] for k, v in one.items()}
