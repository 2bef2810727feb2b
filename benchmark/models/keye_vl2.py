"""Model class `keye_vl2`: how a Keye-VL-2.0-30B-A3B-shaped configuration
file (the source's own HF keys, the language model's) becomes the
program's module — `models/keye.py`'s stack of grouped-query attention
over a LEARNED top-k choice of keys (`models/sparse_indexer.py`,
`ops/sparse_attention.py`) under a per-head QK norm and M-RoPE, beside
softmax-routed SwiGLU expert layers without a shared expert
(`models/moe.py`), under an untied head — its plain reference
(`reference_keye_vl2.py`), and its operation counts.

THE COUNTS ARE OF THE MATHEMATICS: the main attention over the KEPT
pairs (`kept_pairs`: min(topk, t + 1) keys a query), the indexer's
scores over every causal pair, nothing for work an implementation does
on pairs it then masks away — so `device.mfu_pct`, `kernel.attn_roofline`
and `kernel.attn_index_roofline` can only rise toward 100% as the
program stops computing what it masks.

The file's `num_experts` is how many experts are HELD (a chip's share);
the router's width is `share.num_experts_published`.
"""

from __future__ import annotations

import functools

from benchmark import reference_keye_vl2
from benchmark.models import gpt

# q's and k's projections at the seeded state, times the lecun draw
SEEDED_QK_SCALE = 2.0


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.keye import Keye, KeyeConfig

    if config["model_type"] != "KeyeVL2":
        raise ValueError("not a KeyeVL2 configuration")
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
    rope, sa = config["rope_scaling"], config["sa_config"]
    if rope["rope_type"] != "default":
        raise ValueError("the program's M-RoPE is unscaled")
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the program's indexer has ONE shared key")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("kv heads divide the heads")
    if config["train"]["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("the sequence is longer than the positions")
    prog, share = config["program"], config["share"]
    if prog["impl"] != "grouped":
        raise ValueError("a share of the experts exists in the grouped "
                         "path only")
    return Keye(KeyeConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        mrope_sections=tuple(rope["mrope_section"]),
        rms_eps=config["rms_norm_eps"], index_topk=sa["topk"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"],
        index_loss_weight=float(config["train"]["index_loss_weight"]),
        router_aux_loss_weight=float(
            config["train"]["router_aux_loss_coef"]),
        num_experts=share["num_experts_published"],
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        experts_held=config["num_experts"],
        first_expert=share["first_expert"],
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def seeded_leaves(params):
    """`params` with every attention layer's q and k projections times
    `SEEDED_QK_SCALE`: at the lecun draw a head of q or k has an RMS of
    1, which is what its norm would leave alone, and the two scalars the
    check compares could not see a missing per-head norm (PERF.md section
    6, PR 60's finding at LFM2's layer).  The NORMED model computes what
    it computed: an RMSNorm takes a scale out again.  No other leaf is
    scaled: the indexer's scores at the lecun draw already spread by 0.7
    over a row (16 heads of relu'd products of unit-variance lanes), so
    the choice, its top-k and its KL term are no roundings."""
    def scaled(layer):
        attention = dict(layer["attention"])
        for name in ("q_proj", "k_proj"):
            attention[name] = {"kernel": attention[name]["kernel"]
                               * SEEDED_QK_SCALE}
        return {**layer, "attention": attention}

    return {name: scaled(sub) if name.startswith("layers_") else sub
            for name, sub in params.items()}


def seeded_state(trainer, seed: int):
    """`gpt.seeded_state` — every leaf from `seed` in one jitted draw —
    over a draw that ends in `seeded_leaves`: the draw that
    `gpt.seeded_state` keeps on the trainer is made here, before it looks
    for one (as `lfm2_moe.seeded_state`)."""
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
    `over` replaces a size or sets a control (`dtype=`, `wrong=`,
    `parts=True`)."""
    sa = config["sa_config"]
    return functools.partial(
        reference_keye_vl2.loss, **{**dict(
            n_layer=config["num_hidden_layers"],
            n_head=config["num_attention_heads"],
            n_kv=config["num_key_value_heads"], topk=sa["topk"],
            theta=float(config["rope_theta"]),
            sections=tuple(config["rope_scaling"]["mrope_section"]),
            top_k=config["num_experts_per_tok"],
            first_expert=config["share"]["first_expert"],
            eps=config["rms_norm_eps"],
            index_loss_weight=float(config["train"]["index_loss_weight"]),
            aux_weight=float(config["train"]["router_aux_loss_coef"])),
            **over})


# ------------------------------------------------------------- the counts

def kept_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs ONE sequence and head keeps: min(topk, t + 1)
    keys at query t — topk (topk + 1) / 2 + (seq - topk) topk past it."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def _held_rows_per_token(config: dict) -> float:
    """Rows a token sends to the experts held here, EXPECTED under even
    routing (8 x 16 / 128 = 1 at the cell's)."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["share"]["num_experts_published"]


def dense_params_per_token(config: dict) -> dict:
    """Matmul parameters one token passes through, by part: the
    attention's four products (k and v at the kv heads), the indexer's
    three (its input is detached: they get no cotangent of their input,
    which `train_flops_per_token` counts), the router over all published
    experts, the ROUTED experts at the expected rows a token sends to
    the experts held here, the untied head; the embedding is a lookup."""
    h, n = config["hidden_size"], config["num_hidden_layers"]
    d, sa = config["head_dim"], config["sa_config"]
    q = config["num_attention_heads"] * d
    kv = config["num_key_value_heads"] * d
    di = sa["indexer_head_dim"]
    return {
        "attention": n * (2 * h * q + 2 * h * kv),
        "indexer": n * h * (sa["indexer_num_heads"] * (di + 1) + di),
        "router": n * h * config["share"]["num_experts_published"],
        "routed": n * _held_rows_per_token(config) * 3 * h
        * config["moe_intermediate_size"],
        "head": h * config["vocab_size"]}


def attention_pairs_flops_per_token(config: dict) -> float:
    """FORWARD FLOPs a token of the main attention's two products over
    the KEPT pairs, every layer: 2 x (128 + 128) a kept pair of every
    query head."""
    seq = config["train"]["seq_len"]
    return 4.0 * config["head_dim"] * config["num_attention_heads"] \
        * config["num_hidden_layers"] \
        * kept_pairs(seq, config["sa_config"]["topk"]) / seq


def index_pairs_flops_per_token(config: dict) -> float:
    """FORWARD FLOPs a token of the indexer's scores over EVERY causal
    pair, every layer: 2 x 64 a pair and indexer head."""
    seq, sa = config["train"]["seq_len"], config["sa_config"]
    return 2.0 * sa["indexer_head_dim"] * sa["indexer_num_heads"] \
        * config["num_hidden_layers"] * causal_pairs(seq) / seq


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through — 4 for the indexer's, whose input takes
    no cotangent — plus three times the forward FLOPs of the main
    attention's products over the kept pairs and of the indexer's scores
    over the causal ones.  Norms, the rotation, the ReLU and the
    weighting, the choice itself and the KL term's elementwise work are
    left out; recomputation, and whatever an implementation computes on
    pairs it masks away, is not counted."""
    parts = dense_params_per_token(config)
    return 6.0 * sum(parts.values()) - 2.0 * parts["indexer"] \
        + 3.0 * (attention_pairs_flops_per_token(config)
                 + index_pairs_flops_per_token(config))


def attention_cost_per_step(config: dict, global_batch: int,
                            bytes_per_el: int = 2) -> dict:
    """The MAIN attention's FLOPs and least HBM bytes of one optimizer
    step over the whole batch, every layer, forward + backward, over the
    KEPT pairs — `flops.causal_attention_cost`'s counting with
    `kept_pairs` where it has the causal pairs: two products forward and
    four backward at 2 x head_dim FLOPs a kept pair and query head; of
    the bytes, q, o and their gradients once a query head, k, v and
    theirs once a KEY/VALUE head (the selection's own bytes are the
    indexer's, not counted here)."""
    seq, d = config["train"]["seq_len"], config["head_dim"]
    kept = kept_pairs(seq, config["sa_config"]["topk"])
    per_matmul = 2 * d * kept * global_batch * config["num_attention_heads"]
    q_tensor = global_batch * config["num_attention_heads"] * seq * d \
        * bytes_per_el
    kv_tensor = global_batch * config["num_key_value_heads"] * seq * d \
        * bytes_per_el
    one = {"flops_fwd": 2 * per_matmul, "flops_bwd": 4 * per_matmul,
           "flops": 6 * per_matmul,
           "bytes_fwd": 2 * q_tensor + 2 * kv_tensor,
           "bytes_bwd": 4 * q_tensor + 4 * kv_tensor,
           "bytes": 6 * q_tensor + 6 * kv_tensor}
    return {k: v * config["num_hidden_layers"] for k, v in one.items()}


def index_cost_per_step(config: dict, global_batch: int,
                        bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the indexer's SCORES of one optimizer
    step, every layer, forward + backward, over every causal pair — the
    same work whatever implements it.  Forward: one product of 2 x 64
    FLOPs a pair and indexer head; backward: two (the cotangent to the
    heads' queries and to the one key; the recomputed product is the
    implementation's own).  Bytes: forward reads the heads' queries, the
    key and the weights and writes ONE float32 score a causal pair (the
    choice needs every one of them); backward reads a float32 cotangent
    a KEPT pair and writes the three operands' gradients."""
    seq, sa = config["train"]["seq_len"], config["sa_config"]
    heads, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    pairs = causal_pairs(seq) * global_batch
    per_product = 2 * di * heads * pairs
    operands = global_batch * seq * (heads * di + di) * bytes_per_el \
        + global_batch * seq * heads * 4
    kept = kept_pairs(seq, sa["topk"]) * global_batch
    one = {"flops_fwd": per_product, "flops_bwd": 2 * per_product,
           "flops": 3 * per_product,
           "bytes_fwd": operands + 4 * pairs,
           "bytes_bwd": 2 * operands + 4 * kept,
           "bytes": 3 * operands + 4 * pairs + 4 * kept}
    return {k: v * config["num_hidden_layers"] for k, v in one.items()}


def moe_cost_per_step(config: dict, global_batch: int,
                      bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the HELD experts' matmuls of one
    optimizer step, forward + backward, all layers, at the expected rows
    (`_held_rows_per_token`), as `kimi_vl.moe_cost_per_step` counts
    them."""
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
    return {k: v * config["num_hidden_layers"] for k, v in one.items()}
