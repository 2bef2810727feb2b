"""Model class `sdar_moe`: how an SDAR-30B-A3B-Chat-shaped configuration
file (the source's own HF keys) becomes the program's module —
`models/sdar.py`'s block-diffusion training of a Qwen3-MoE trunk: a clean
and a noised copy of every sequence through grouped-query attention under
a per-head QK norm and a static block mask (`ops/block_attention.py`),
softmax-routed SwiGLU expert layers without a shared expert
(`models/moe.py`), an untied head on the noised copy alone, a loss on the
masked positions weighed by 1 / t — its plain reference
(`reference_sdar_moe.py`), and its operation counts.

THE COUNTS ARE OF THE MATHEMATICS, per DATA token (`tokens_per_s` counts
those: `train.seq_len` a sequence, of which 2 x as many POSITIONS run):
both copies through every product of every block (a pipeline stage hands
both on), the head on the noised copy's T rows, the attention over the
KEPT pairs (`kept_pairs`: T^2 + T L a head and sequence), nothing for
pairs an implementation computes and then masks — so `device.mfu_pct`
and `kernel.attn_roofline` (which reads every `dwt_fa_` kernel against
`attention_cost_per_step`: in this class's cells the `dwt_fa_bd_*`
kernels' share of their roofline) can only rise toward 100% as the
program stops computing what it masks.

The file's `num_experts` is how many experts are HELD (a chip's share);
the router's width is `share.num_experts_published`.
"""

from __future__ import annotations

import functools

from benchmark import reference_sdar_moe
from benchmark.models import gpt, keye_vl2

SEEDED_QK_SCALE = keye_vl2.SEEDED_QK_SCALE  # q's and k's, times the draw
COPIES = 2  # positions a data token: the clean copy's and the noised one's


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.sdar import SDAR, SDARConfig

    if config["model_type"] != "sdar_moe":
        raise ValueError("not an sdar_moe configuration")
    if not config["norm_topk_prob"]:
        raise ValueError("the program's router normalises the chosen gates")
    if config["attention_bias"] or config["use_sliding_window"]:
        raise ValueError("the program's attention has no bias and no window")
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise ValueError("every layer's feed-forward is an expert layer")
    if config["tie_word_embeddings"]:
        raise ValueError("the program's head is untied")
    if config["hidden_act"] != "silu":
        raise ValueError("the program's experts are SwiGLU")
    if config["rope_scaling"] is not None:
        raise ValueError("the program's rotation is unscaled")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("kv heads divide the heads")
    train = config["train"]
    if train["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("the sequence is longer than the positions")
    prog, share = config["program"], config["share"]
    if prog["impl"] != "grouped":
        raise ValueError("a share of the experts exists in the grouped "
                         "path only")
    return SDAR(SDARConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        block_length=train["block_length"], noise_eps=train["noise_eps"],
        noise_seed=train["noise_seed"], mask_token_id=mask_id(config),
        router_aux_loss_weight=float(train["router_aux_loss_coef"]),
        num_experts=share["num_experts_published"],
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        experts_held=config["num_experts"],
        first_expert=share["first_expert"],
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def mask_id(config: dict) -> int:
    """MASK: the last row of the held slice of the vocabulary."""
    return config["vocab_size"] - 1


def seeded_leaves(params):
    """`keye_vl2.seeded_leaves` (every attention layer's q and k
    projections times `SEEDED_QK_SCALE`: the check can then see a missing
    per-head norm) over a draw whose EMBEDDING stands at an entry's RMS
    of 1, not the draw's 1 / sqrt(hidden) = 0.022: beside the branches'
    outputs and not a fiftieth of them (as `xing4_0.seeded_leaves` sets
    its own, for the same reason).  At the draw's scale the attention's
    output — a mean over thousands of near-uniform keys, the same vector
    at every position — drowns the token's own part of the stream by the
    third block, every position routes alike (`moe.load_max_over_mean`
    16 = 128 / 8: ONE set of experts a layer), and whether those eight
    are among the sixteen held here is a coin a seed: this chip's rows,
    and the step's time, then swing by seed and by step (my chip runs,
    PR 70: 4.5% to 19.4% of the rows inside one window).  A trained
    model's stream is the token's; no other leaf is scaled, and the
    reference is given the same parameters."""
    table = params["embed_tokens"]["embedding"]
    return keye_vl2.seeded_leaves({**params, "embed_tokens": {
        "embedding": table * table.shape[1] ** 0.5}})


def seeded_state(trainer, seed: int):
    """`gpt.seeded_state` — every leaf from `seed` in one jitted draw —
    over a draw that ends in `seeded_leaves`: the draw that
    `gpt.seeded_state` keeps on the trainer is made here, before it looks
    for one (as `keye_vl2.seeded_state`)."""
    import jax

    from dlrover_wuqiong_tpu.trainer.train_step import TrainState

    if getattr(trainer, "_bench_seeded_init", None) is None:
        model, optimizer = trainer.res.model, trainer.optimizer
        trainer._bench_seeded_init = jax.jit(
            lambda key: TrainState.create(
                seeded_leaves(model.init_params(key)), optimizer),
            out_shardings=trainer.res.state_shardings)
    return gpt.seeded_state(trainer, seed)


def reference_loss(config: dict, **over):
    """`loss(params, batch)` of the plain reference for this file;
    `over` replaces a size or sets a control (`dtype=`, `wrong=`)."""
    train = config["train"]
    return functools.partial(
        reference_sdar_moe.loss, **{**dict(
            n_layer=config["num_hidden_layers"],
            n_head=config["num_attention_heads"],
            n_kv=config["num_key_value_heads"],
            theta=float(config["rope_theta"]),
            top_k=config["num_experts_per_tok"],
            first_expert=config["share"]["first_expert"],
            eps=config["rms_norm_eps"],
            noise_seed=train["noise_seed"],
            block_length=train["block_length"],
            noise_eps=train["noise_eps"], mask_id=mask_id(config),
            aux_weight=float(train["router_aux_loss_coef"])), **over})


# ------------------------------------------------------------- the counts

def kept_pairs(seq: int, block_length: int) -> int:
    """(query, key) pairs ONE sequence and head keeps over its 2 x seq
    positions: seq (seq + L) / 2 clean to clean, seq (seq - L) / 2 noised
    to clean, seq L noised to noised = seq^2 + seq L."""
    return seq * seq + seq * block_length


def _held_rows_per_position(config: dict) -> float:
    """Rows a POSITION sends to the experts held here, EXPECTED under
    even routing (8 x 16 / 128 = 1 at the cell's)."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["share"]["num_experts_published"]


def dense_params_per_token(config: dict) -> dict:
    """Matmul parameters one DATA token passes through, by part: both its
    copies through the attention's four products (k and v at the kv
    heads), the router over all published experts and the ROUTED experts
    at the expected rows a position sends to the experts held here; the
    untied head once (the noised copy's row); the embedding is a
    lookup."""
    h, n = config["hidden_size"], config["num_hidden_layers"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return {
        "attention": COPIES * n * (2 * h * q + 2 * h * kv),
        "router": COPIES * n * h * config["share"]["num_experts_published"],
        "routed": COPIES * n * _held_rows_per_position(config) * 3 * h
        * config["moe_intermediate_size"],
        "head": h * config["vocab_size"]}


def attention_pairs_flops_per_token(config: dict) -> float:
    """FORWARD FLOPs a data token of the attention's two products over
    the KEPT pairs, every layer: 2 x (128 + 128) a kept pair of every
    query head."""
    seq = config["train"]["seq_len"]
    return 4.0 * config["head_dim"] * config["num_attention_heads"] \
        * config["num_hidden_layers"] \
        * kept_pairs(seq, config["train"]["block_length"]) / seq


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one DATA token requires: 6 per matmul
    parameter it passes through plus three times the forward FLOPs of the
    attention's products over the kept pairs.  Norms, the rotation, the
    noising and the loss's elementwise work are left out; recomputation,
    and whatever an implementation computes on pairs it masks away, is
    not counted."""
    return 6.0 * sum(dense_params_per_token(config).values()) \
        + 3.0 * attention_pairs_flops_per_token(config)


def attention_cost_per_step(config: dict, global_batch: int,
                            bytes_per_el: int = 2) -> dict:
    """The attention's FLOPs and least HBM bytes of one optimizer step
    over the whole batch, every layer, forward + backward, over the KEPT
    pairs — `flops.causal_attention_cost`'s counting with `kept_pairs`
    where it has the causal pairs: two products forward and four backward
    at 2 x head_dim FLOPs a kept pair and query head; of the bytes, q, o
    and their gradients once a query head, k, v and theirs once a
    KEY/VALUE head, each over BOTH copies' positions."""
    seq, d = config["train"]["seq_len"], config["head_dim"]
    kept = kept_pairs(seq, config["train"]["block_length"])
    per_matmul = 2 * d * kept * global_batch * config["num_attention_heads"]
    q_tensor = global_batch * config["num_attention_heads"] * COPIES * seq \
        * d * bytes_per_el
    kv_tensor = global_batch * config["num_key_value_heads"] * COPIES * seq \
        * d * bytes_per_el
    one = {"flops_fwd": 2 * per_matmul, "flops_bwd": 4 * per_matmul,
           "flops": 6 * per_matmul,
           "bytes_fwd": 2 * q_tensor + 2 * kv_tensor,
           "bytes_bwd": 4 * q_tensor + 4 * kv_tensor,
           "bytes": 6 * q_tensor + 6 * kv_tensor}
    return {k: v * config["num_hidden_layers"] for k, v in one.items()}


def moe_cost_per_step(config: dict, global_batch: int,
                      bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the HELD experts' matmuls of one
    optimizer step, forward + backward, all layers, at the expected rows
    of BOTH copies (`_held_rows_per_position`), as
    `keye_vl2.moe_cost_per_step` counts them."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    rows = int(global_batch * COPIES * config["train"]["seq_len"]
               * _held_rows_per_position(config))
    per_matmul = 2 * rows * h * f
    row_bytes = rows * h * bytes_per_el
    weight_bytes = 3 * config["num_experts"] * h * f * bytes_per_el
    one = {"flops_fwd": 3 * per_matmul, "flops_bwd": 6 * per_matmul,
           "flops": 9 * per_matmul,
           "bytes_fwd": 2 * row_bytes + weight_bytes,
           "bytes_bwd": 3 * row_bytes + 2 * weight_bytes,
           "bytes": 5 * row_bytes + 3 * weight_bytes}
    return {k: v * config["num_hidden_layers"] for k, v in one.items()}
