"""Model class `qwen3_next`: how a Qwen3-Next-80B-A3B-shaped configuration
file (the source's own HF keys) becomes the program's module —
`models/qwen3_next.py`'s periods of gated delta-rule mixers whose value
heads outnumber their key heads (`models/gated_delta.py`,
`ops/delta_rule.py`) and one output-gated grouped-query attention of
heads rotated in part (`models/llama.py`), every block followed by a
softmax-routed expert layer beside a sigmoid-gated shared expert
(`models/moe.py`), zero-centred norms, an untied head — its plain
reference (`reference_qwen3_next.py`), and its operation counts.

THE COUNTS ARE OF THE MODEL: the recurrence at its 16 key heads and 32
value heads whatever the route moves (q and k repeated to the value
heads in HBM cost time, not work), the attention over its causal pairs.

The file's `num_experts` is how many experts are HELD (a chip's share);
the router's width is `share.num_experts_published`.
"""

from __future__ import annotations

import functools

from benchmark import flops, reference_qwen3_next
from benchmark.models import gpt, keye_vl2

# the standard deviation the zero-centred norms' w is drawn at in the
# seeded state (the program's own draw is 0: `1 + w` = 1)
SEEDED_NORM_STD = 0.2
# the scale of the gate half of an attention layer's `q_proj` there
SEEDED_GATE_SCALE = 4.0


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.qwen3_next import (
        Qwen3Next,
        Qwen3NextConfig,
    )

    if config["model_type"] != "qwen3_next":
        raise ValueError("not a qwen3_next configuration")
    if not config["norm_topk_prob"]:
        raise ValueError("the program's router normalises the chosen gates")
    if config["use_sliding_window"] or config["rope_scaling"] is not None:
        raise ValueError("the program's attention has no window and an "
                         "unscaled rotation")
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise ValueError("every layer's feed-forward is an expert layer")
    if config["tie_word_embeddings"]:
        raise ValueError("the program's head is untied")
    if config["hidden_act"] != "silu":
        raise ValueError("the program's experts are SwiGLU")
    if config["num_attention_heads"] % config["num_key_value_heads"] \
            or config["linear_num_value_heads"] \
            % config["linear_num_key_heads"]:
        raise ValueError("kv heads divide the heads, key heads the value "
                         "heads")
    rotary = config["head_dim"] * config["partial_rotary_factor"]
    if rotary != int(rotary) or int(rotary) % 2:
        raise ValueError("the rotated part of a head is whole pairs")
    prog, share = config["program"], config["share"]
    if prog["impl"] != "grouped":
        raise ValueError("a share of the experts exists in the grouped "
                         "path only")
    if config["train"]["seq_len"] > config["max_position_embeddings"] \
            or config["train"]["seq_len"] % prog["delta_chunk_size"]:
        raise ValueError("the sequence is longer than the positions, or "
                         "no multiple of the delta rule's chunk")
    return Qwen3Next(Qwen3NextConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        full_attention_interval=config["full_attention_interval"],
        max_seq_len=config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rotary_dim=int(rotary),
        rope_theta=float(config["rope_theta"]),
        linear_key_heads=config["linear_num_key_heads"],
        linear_value_heads=config["linear_num_value_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        chunk_size=prog["delta_chunk_size"],
        num_experts=share["num_experts_published"],
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["shared_expert_intermediate_size"],
        experts_held=config["num_experts"],
        first_expert=share["first_expert"],
        router_aux_loss_weight=float(
            config["train"]["router_aux_loss_coef"]),
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def _wide_gates(attention: dict) -> dict:
    """An attention layer's `q_proj` with each head's GATE half times
    `SEEDED_GATE_SCALE` (a head's 2 x d lanes are `[query | gate]`)."""
    import jax.numpy as jnp

    q = attention["q_proj"]["kernel"]
    d = attention["q_norm"]["scale"].shape[0]
    halves = q.reshape(q.shape[0], -1, 2, d)
    both = halves * jnp.asarray([1.0, SEEDED_GATE_SCALE],
                                q.dtype)[:, None]
    return {**attention, "q_proj": {"kernel": both.reshape(q.shape)}}


def seeded_leaves(params, key):
    """`params` as the check needs them, so that the model's equations
    move the two scalars it compares (the program computes what it
    computed: these are parameter values, not code):

    - every zero-centred norm's w drawn from `key` at `SEEDED_NORM_STD`
      where the program's draw is 0: at w = 0 the form `(1 + w)` is the
      function the plain form is at w = 1, and neither the check nor the
      weight decay's pull could tell them apart;
    - the gate half of the attention's `q_proj` times
      `SEEDED_GATE_SCALE`: logits of standard deviation 4 open and close
      single lanes, which one number a head cannot — at the lecun draw
      the control `head_gate` read 1.9e-5 | 1.7e-4 beside a sound 1.2e-5
      | 1.7e-5, with the scale 7.5e-5 | 1.7e-3 (my chip runs, PR 66,
      calls 1 and 3, seed 2147490001).

    No other leaf is touched: `shared_expert_gate` and the mixers' a, b
    and z are lecun draws whose logits have unit variance, so no gate
    sits at an inert 0.5 (the counters read their means).  What the two
    scalars still cannot see is the rotation's extent (`full_rotary`): at
    any random draw a score is noise whatever its position; turning the
    query heads toward their keys did not change that (call 3) and was
    taken out again."""
    import jax

    keys = iter(jax.random.split(key, len(jax.tree.leaves(params))))

    def drawn(path, leaf):
        k = next(keys)
        # every `scale` is a zero-centred norm's w: the block norms, the
        # final one, the q and k head norms (the mixer's plain output
        # norm is `gate_norm_scale`)
        if path[-1].key != "scale":
            return leaf
        return leaf + SEEDED_NORM_STD * jax.random.normal(
            k, leaf.shape, leaf.dtype)

    params = jax.tree_util.tree_map_with_path(drawn, params)
    return {name: {**sub, "attention": _wide_gates(sub["attention"])}
            if "attention" in sub else sub for name, sub in params.items()}


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
                seeded_leaves(model.init_params(key),
                              jax.random.fold_in(key, 1)), optimizer),
            out_shardings=trainer.res.state_shardings)
    return gpt.seeded_state(trainer, seed)


def reference_loss(config: dict, **over):
    """`loss(params, batch)` of the plain reference for this file;
    `over` replaces a size or sets a control (`dtype=`, `wrong=`)."""
    return functools.partial(
        reference_qwen3_next.loss, **{**dict(
            n_layer=config["num_hidden_layers"],
            interval=config["full_attention_interval"],
            n_head=config["num_attention_heads"],
            n_kv=config["num_key_value_heads"],
            theta=float(config["rope_theta"]),
            rotary=config["partial_rotary_factor"],
            key_heads=config["linear_num_key_heads"],
            value_heads=config["linear_num_value_heads"],
            key_dim=config["linear_key_head_dim"],
            value_dim=config["linear_value_head_dim"],
            top_k=config["num_experts_per_tok"],
            first_expert=config["share"]["first_expert"],
            eps=config["rms_norm_eps"],
            aux_weight=float(config["train"]["router_aux_loss_coef"])),
            **over})


# ------------------------------------------------------------- the counts

def _counts(config: dict) -> tuple:
    """(gated delta-rule layers, attention layers)."""
    kinds = reference_qwen3_next.layer_kinds(
        config["num_hidden_layers"], config["full_attention_interval"])
    return kinds.count("linear_attention"), kinds.count("full_attention")


# rows a token sends to the experts held here, EXPECTED under even routing
# (10 x 16 / 512 = 0.3125 at the cell's), and the held experts' matmuls of
# one step at those rows: the counts of another share of softmax-routed
# SwiGLU experts in every layer, from the same keys
_held_rows_per_token = keye_vl2._held_rows_per_token
moe_cost_per_step = keye_vl2.moe_cost_per_step


def dense_params_per_token(config: dict) -> dict:
    """Matmul parameters one token passes through, by part: a delta
    mixer's seven products (q and k at the KEY heads), the attention's
    four (q twice as wide: it carries the gate; k and v at the kv heads),
    the router over all published experts, the shared expert and its
    gate's vector, the ROUTED experts at the expected rows a token sends
    to the experts held here, the untied head; the embedding is a
    lookup."""
    h, n = config["hidden_size"], config["num_hidden_layers"]
    n_lin, n_attn = _counts(config)
    qk = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    heads = config["linear_num_value_heads"]
    v = heads * config["linear_value_head_dim"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return {
        "linear": n_lin * (h * (2 * qk + 2 * v + 2 * heads) + v * h),
        "attention": n_attn * (3 * h * q + 2 * h * kv),
        "router": n * h * config["share"]["num_experts_published"],
        "shared": n * (3 * h * config["shared_expert_intermediate_size"]
                       + h),
        "routed": n * _held_rows_per_token(config) * 3 * h
        * config["moe_intermediate_size"],
        "head": h * config["vocab_size"]}


def _recurrence_flops_per_token(config: dict) -> int:
    """The RECURRENCE's operations of one gated delta-rule layer for one
    token, forward, as its equation is written: per VALUE head the decay
    of S (dk*dv products), S^T k (2*dk*dv), the outer product k u^T
    (dk*dv), its sum into S (dk*dv) and o = S^T q (2*dk*dv): 7*dk*dv a
    head, as `olmo_hybrid._recurrence_flops_per_token` counts it.  What a
    chunked form spends beyond that is that form's own cost."""
    return 7 * config["linear_key_head_dim"] \
        * config["linear_value_head_dim"] * config["linear_num_value_heads"]


def attention_pairs_flops_per_token(config: dict) -> float:
    """FORWARD FLOPs a token of the attention's two products over the
    causal pairs, the attention layers: 2 x (d + d) a pair and query
    head."""
    seq = config["train"]["seq_len"]
    return 4.0 * config["head_dim"] * config["num_attention_heads"] \
        * _counts(config)[1] * (seq + 1) / 2


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through (`dense_params_per_token`) plus three
    times the forward FLOPs of the attention's products over the causal
    pairs and of the recurrence.  The convolution, norms, gates and the
    rotation are left out; recomputation is not counted."""
    return 6.0 * sum(dense_params_per_token(config).values()) \
        + 3.0 * attention_pairs_flops_per_token(config) \
        + 3.0 * _counts(config)[0] * _recurrence_flops_per_token(config)


def attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """Causal attention FLOPs and bytes of one optimizer step over the
    whole batch, the attention layers, as `lfm2_moe.py` counts grouped
    heads: FLOPs of every query head; of the bytes, k, v and their
    gradients once a KEY/VALUE head, q, o and theirs once a query head."""
    seq, d = config["train"]["seq_len"], config["head_dim"]
    per_q = flops.causal_attention_cost(
        global_batch, config["num_attention_heads"], seq, d, bytes_per_el=2)
    per_kv = flops.causal_attention_cost(
        global_batch, config["num_key_value_heads"], seq, d, bytes_per_el=2)
    one = {k: v for k, v in per_q.items() if k.startswith("flops")}
    for k in ("bytes_fwd", "bytes_bwd", "bytes"):
        one[k] = (per_q[k] + per_kv[k]) // 2  # half the tensors are k, v
    return {k: v * _counts(config)[1] for k, v in one.items()}


def delta_cost_per_step(config: dict, global_batch: int,
                        bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the gated delta rule of one optimizer
    step, forward + backward, all `linear_attention` layers, counted at
    the MODEL's heads whatever the route moves.

    FLOPs: the RECURRENCE's (`_recurrence_flops_per_token`), twice that
    backward.  Bytes: q and k once a KEY head (Hk*dk each), v and o once a
    VALUE head (H*dv each), the decay and the write gate (H each), read
    or written once forward and once more backward (their gradients), at
    `bytes_per_el`; a state that never leaves the chip's fast memory.
    Both err low: the share of the roofline this gives cannot pass 100%
    by a later change of form, and a route that repeats q and k to the
    value heads moves bytes this does not count."""
    tokens = global_batch * config["train"]["seq_len"]
    heads = config["linear_num_value_heads"]
    one_way = tokens * (
        2 * config["linear_num_key_heads"] * config["linear_key_head_dim"]
        + 2 * heads * config["linear_value_head_dim"] + 2 * heads) \
        * bytes_per_el
    fwd = tokens * _recurrence_flops_per_token(config)
    one = {"flops_fwd": fwd, "flops_bwd": 2 * fwd, "flops": 3 * fwd,
           "bytes_fwd": one_way, "bytes_bwd": one_way, "bytes": 2 * one_way}
    return {k: v * _counts(config)[0] for k, v in one.items()}
