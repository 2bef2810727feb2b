"""Model class `bailing_hybrid`: how a Ling-3.0-flash-shaped configuration
file (the source's own HF keys) becomes the program's module —
`models/bailing_hybrid.py`'s stack of KDA mixers (`models/kda.py`,
`ops/delta_rule.py` with a decay a channel) beside gated latent attention
(`models/latent_attention.py`), a leading dense SwiGLU layer and
sigmoid-routed SwiGLU expert layers under a group limit with a shared
expert (`models/moe.py`) — its plain reference
(`reference_bailing_hybrid.py`), and its operation counts.

The file's `num_experts` is how many experts are HELD and its
`num_attention_heads` how many heads of every mixer (a chip's share);
the router's width is `share.num_experts_published`.
"""

from __future__ import annotations

import functools

from benchmark import reference_bailing_hybrid
from benchmark.models import gpt, kimi_vl, nemotron_h

_BALANCE_ROUNDS = kimi_vl._BALANCE_ROUNDS
_SOLVE_ITERS = 30


def _kept_layers(config: dict, key: str) -> list:
    return config[key][:config["num_hidden_layers"]]


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.bailing_hybrid import (
        BailingHybrid,
        BailingHybridConfig,
    )

    if config["model_type"] != "bailing_hybrid":
        raise ValueError("not a bailing_hybrid configuration")
    if (config["hidden_act"], config["score_function"],
            config["scoring_func"], config["topk_method"]) != \
            ("silu", "sigmoid", "sigmoid", "noaux_tc") \
            or not config["moe_router_enable_expert_bias"]:
        raise ValueError("the program's experts are SwiGLU, its router a "
                         "sigmoid with a selection bias")
    if not config["norm_topk_prob"] or config["scale_router_input"]:
        raise ValueError("the program's router normalises the chosen gates "
                         "and reads its input unscaled")
    if any(_kept_layers(config, "expert_swiglu_limit_list")) \
            or any(_kept_layers(config, "share_expert_swiglu_limit_list")):
        raise ValueError("a clamp on an expert's SwiGLU is not built: the "
                         "kept layers' limits must be 0")
    if config["num_nextn_predict_layers"] \
            and config["mtp_loss_scaling_factor"]:
        raise ValueError("a multi-token-prediction module with a weight in "
                         "the loss is not built on this stack")
    if not (config["kda_safe_gate"] and config["no_kda_lora"]
            and config["linear_silu"]) or config["use_kda_lora"]:
        raise ValueError("the program's KDA decay is the safe gate of ONE "
                         "full projection behind a silu'd convolution")
    if config["num_kv_heads_for_linear_attn"] \
            or config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("every mixer has one key head a query head")
    if config["gated_attention_proj_granularity_type"] != "head_wise" \
            or config["group_norm_size"] != 1:
        raise ValueError("the output gate is one number a head, the output "
                         "norm one scale for all heads")
    if config["q_lora_rank"] is not None or config["rope_scaling"] is not None:
        raise ValueError("the latent layer has no q latent and its RoPE no "
                         "scaling")
    if config["rotary_dim"] != config["qk_rope_head_dim"] \
            or config["qk_head_dim"] != config["qk_nope_head_dim"] \
            + config["qk_rope_head_dim"]:
        raise ValueError("the rotated lanes are the rope part of a key")
    if config["use_bias"] or config["use_qkv_bias"] \
            or config["tie_word_embeddings"] or config["use_nGPT"] \
            or config["up_proj_norm"] or config["value_norm"] \
            or config["use_mla_nope"] or not config["use_qk_norm"]:
        raise ValueError("no bias anywhere, an untied head, KDA's L2 norms "
                         "and no other norm inside a mixer")
    if config["moe_shared_expert_intermediate_size"] \
            != config["moe_intermediate_size"]:
        raise ValueError("the shared expert is num_shared_experts experts "
                         "of the routed width")
    if not 0 < config["first_k_dense_replace"] <= config["num_hidden_layers"]:
        raise ValueError("the leading dense layers lie inside the depth")
    prog, share = config["program"], config["share"]
    if config["train"]["seq_len"] > config["max_position_embeddings"] \
            or config["train"]["seq_len"] % prog["delta_chunk_size"]:
        raise ValueError("the sequence is longer than the positions, or no "
                         "multiple of the delta rule's chunk")
    if prog["impl"] != "grouped":
        raise ValueError("a share of the experts exists in the grouped "
                         "path only")
    return BailingHybrid(BailingHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        layer_group_size=config["layer_group_size"],
        first_dense_layers=config["first_k_dense_replace"],
        dense_width=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        linear_key_dim=config["head_dim"],
        linear_value_dim=config["head_dim"],
        conv_kernel=config["short_conv_kernel_size"],
        chunk_size=prog["delta_chunk_size"],
        kda_lower_bound=float(config["kda_lower_bound"]),
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        kv_lora_rank=config["kv_lora_rank"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        num_experts=share["num_experts_published"],
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_experts=config["num_shared_experts"],
        routed_scaling=config["routed_scaling_factor"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        experts_held=config["num_experts"],
        first_expert=share["first_expert"],
        bias_update_rate=config["train"]["selection_bias_update_rate"],
        swiglu_limits=tuple(
            _kept_layers(config, "expert_swiglu_limit_list")
            + _kept_layers(config, "share_expert_swiglu_limit_list")),
        mtp_layers=config["num_nextn_predict_layers"],
        mtp_loss_weight=float(config["mtp_loss_scaling_factor"]),
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def balanced_bias(scores, top_k: int, n_group: int, topk_group: int,
                  iters: int = _SOLVE_ITERS):
    """`nemotron_h.balanced_bias` under the group limit: a selection bias
    (E,), mean 0, under which every expert is among a token's `top_k`
    INSIDE its kept groups for the same number of the T tokens, to within
    the ties.  Each pass holds every token's kept groups and its threshold
    (its k-th largest biased score inside them) and moves each expert's
    bias half of the way to where T * top_k / E of the tokens that keep
    its group lie over their thresholds."""
    import jax
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.moe import limit_to_groups

    n_tok, n_exp = scores.shape
    want = n_tok * top_k // n_exp

    def one_pass(bias, _):
        limited = limit_to_groups(scores + bias, n_group, topk_group)
        kth = jax.lax.top_k(limited, top_k)[0][:, -1]
        over = jnp.sort(jnp.where(limited == -jnp.inf, -jnp.inf,
                                  scores - kth[:, None]), axis=0)
        level = -(over[n_tok - want] + over[n_tok - want - 1]) / 2
        # an expert whose group fewer than `want` tokens keep: leave it
        level = jnp.where(jnp.isfinite(level), level, bias)
        bias = bias + 0.5 * (level - bias)
        return bias - bias.mean(), ()

    return jax.lax.scan(one_pass, jnp.zeros((n_exp,), scores.dtype), (),
                        length=iters)[0]


def seeded_state(trainer, seed: int):
    """The train state drawn from `seed` as every model class's is
    (`models/gpt.py::seeded_state`), and then each expert layer's
    selection bias set to where the out-of-band rule settles on the
    seed's first batch (`balanced_bias`), as `kimi_vl.seeded_state` does
    without a group limit."""
    import jax
    import numpy as np

    state = gpt.seeded_state(trainer, seed)
    model = trainer.res.model
    cfg = model.config
    layers = kimi_vl._expert_layers(state.params)
    done = getattr(trainer, "_bench_balanced", None)
    if done is None or done[0] != seed:
        one_round = getattr(trainer, "_bench_balance_round", None)
        if one_round is None:  # traced once a process

            def one_round(params, ids):
                _, found = model.apply(
                    {"params": params}, ids, mutable=["intermediates"],
                    capture_intermediates=lambda m, _: m.name == "router")
                found = found["intermediates"]
                return {name: balanced_bias(jax.nn.sigmoid(
                    found[name]["feed_forward"]["router"]["__call__"][0]),
                    cfg.top_k, cfg.n_group, cfg.topk_group)
                    for name in layers}

            one_round = trainer._bench_balance_round = jax.jit(one_round)
        data = getattr(trainer.train_data, "inner", trainer.train_data)
        ids = trainer.res.place_batch(dict(data(0)))["input_ids"]
        params, biases = state.params, {}
        for _ in range(_BALANCE_ROUNDS):
            biases = one_round(params, ids)
            params = nemotron_h._with_biases(state.params, biases)
        # on the host: the step donates whatever the state holds
        trainer._bench_balanced = done = (
            seed, {name: np.asarray(b) for name, b in biases.items()})
    state = state._replace(
        params=nemotron_h._with_biases(state.params, done[1]))
    trainer.res.state = state
    return state


def reference_loss(config: dict, **over):
    """`loss(params, batch)` of the plain reference for this file;
    `over` replaces a size or sets a control (`dtype=`, `wrong=`)."""
    return functools.partial(
        reference_bailing_hybrid.loss, **{**dict(
            n_layer=config["num_hidden_layers"],
            group_size=config["layer_group_size"],
            first_dense=config["first_k_dense_replace"],
            heads=config["num_attention_heads"],
            lower_bound=float(config["kda_lower_bound"]),
            nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
            theta=float(config["rope_theta"]),
            top_k=config["num_experts_per_tok"], n_group=config["n_group"],
            topk_group=config["topk_group"],
            routed_scaling=config["routed_scaling_factor"],
            first_expert=config["share"]["first_expert"],
            eps=config["rms_norm_eps"]), **over})


def _counts(config: dict) -> tuple:
    """(KDA layers, latent layers, leading dense layers, expert layers)."""
    depth, group = config["num_hidden_layers"], config["layer_group_size"]
    latent = sum((i + 1) % group == 0 for i in range(depth))
    dense = config["first_k_dense_replace"]
    return depth - latent, latent, dense, depth - dense


def _held_rows_per_token(config: dict) -> float:
    """Rows a token sends to the experts held here, EXPECTED under even
    routing (8 x 8 / 512 = 0.125 at the cell's)."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["share"]["num_experts_published"]


def _head_lanes(config: dict) -> tuple:
    """(q's and k's lanes a head of the latent layer, v's)."""
    return config["qk_head_dim"], config["v_head_dim"]


def dense_params_per_token(config: dict) -> dict:
    """Matmul parameters one token passes through, by part, at the HELD
    heads: a KDA mixer's seven products (q, k, v, the decay's f, the write
    gate's b, the output gate's, o), the latent layer's five (q, the
    down-projection to latent and rope key, the up-projection, the
    gate's, o), the leading dense SwiGLU, the expert layers (router over
    all published experts, shared expert, ROUTED experts at the expected
    rows a token sends to the experts held here), the untied head; the
    embedding is a lookup."""
    h, heads, d = (config["hidden_size"], config["num_attention_heads"],
                   config["head_dim"])
    qk, v = _head_lanes(config)
    rank = config["kv_lora_rank"]
    n_kda, n_latent, dense, expert = _counts(config)
    f = config["moe_intermediate_size"]
    kda = h * (4 * heads * d + 2 * heads) + heads * d * h
    latent = h * heads * qk + h * (rank + config["qk_rope_head_dim"]) \
        + rank * heads * (config["qk_nope_head_dim"] + v) + heads * v * h \
        + h * heads
    return {
        "kda": n_kda * kda, "latent": n_latent * latent,
        "dense": dense * 3 * h * config["intermediate_size"],
        "router": expert * h * config["share"]["num_experts_published"],
        "shared": expert * 3 * h * config["num_shared_experts"] * f,
        "routed": expert * _held_rows_per_token(config) * 3 * h * f,
        "head": h * config["vocab_size"]}


def _recurrence_flops_per_token(config: dict) -> int:
    """The RECURRENCE's operations of one KDA layer for one token,
    forward, as its equation is written, per HELD head: Diag(alpha) S
    (dk*dv products: a decay a channel costs what a decay a head does),
    S^T k (2*dk*dv), the outer product k u^T (dk*dv), its sum into S
    (dk*dv) and o = S^T q (2*dk*dv): 7*dk*dv a head.  What a chunked form
    spends beyond that is that form's own cost, not counted."""
    return 7 * config["head_dim"] ** 2 * config["num_attention_heads"]


def attention_pairs_flops_per_token(config: dict) -> float:
    """FORWARD FLOPs a token of the attention kernels' two products, the
    latent layers: 2 x (192 + 128) a kept pair of every held head."""
    seq = config["train"]["seq_len"]
    return 2.0 * sum(_head_lanes(config)) * config["num_attention_heads"] \
        * _counts(config)[1] * (seq + 1) / 2


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through (`dense_params_per_token`) plus three
    times the attention products' and the recurrence's forward FLOPs.
    The convolution, norms, RoPE and gates are left out; recomputation is
    not counted."""
    return 6.0 * sum(dense_params_per_token(config).values()) \
        + 3.0 * attention_pairs_flops_per_token(config) \
        + 3.0 * _counts(config)[0] * _recurrence_flops_per_token(config)


def attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """Attention FLOPs and least HBM bytes of one optimizer step over the
    whole batch, the latent layers, on kept pairs, as
    `kimi_vl.attention_cost_per_step` counts them: S, dQ and dK over 192
    lanes a pair, O, dV and dP over 128; q, k, dq, dk moved at 192 and v,
    o, dO, dv at 128, bf16, a held head."""
    seq, heads = config["train"]["seq_len"], config["num_attention_heads"]
    qk, v = _head_lanes(config)
    layers = _counts(config)[1]
    pairs = seq * (seq + 1) // 2 * global_batch * heads * layers
    rows = global_batch * heads * seq * layers * 2  # bytes of one lane
    return {"flops_fwd": 2 * (qk + v) * pairs,
            "flops_bwd": 2 * (2 * qk + 2 * v) * pairs,
            "flops": 6 * (qk + v) * pairs,
            "bytes_fwd": (2 * qk + 2 * v) * rows,
            "bytes_bwd": (4 * qk + 4 * v) * rows,
            "bytes": (6 * qk + 6 * v) * rows}


def delta_cost_per_step(config: dict, global_batch: int,
                        bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the delta rule of one optimizer step,
    forward + backward, all KDA layers, the HELD heads.

    FLOPs: the RECURRENCE's (`_recurrence_flops_per_token`: 7*dk*dv a
    head a token forward), twice that backward — not the chunked form's.
    Bytes: each of q, k and the DECAY (H*dk: a number a channel), v and o
    (H*dv) and the write gate (H) read or written once forward, and once
    more backward (their gradients), at `bytes_per_el`; a state that never
    leaves the chip's fast memory.  Both err low: the share of the
    roofline this gives cannot pass 100% by a later change of form."""
    tokens = global_batch * config["train"]["seq_len"]
    heads, d = config["num_attention_heads"], config["head_dim"]
    one_way = tokens * heads * (3 * d + 2 * d + 1) * bytes_per_el
    fwd = tokens * _recurrence_flops_per_token(config)
    one = {"flops_fwd": fwd, "flops_bwd": 2 * fwd, "flops": 3 * fwd,
           "bytes_fwd": one_way, "bytes_bwd": one_way, "bytes": 2 * one_way}
    return {k: v * _counts(config)[0] for k, v in one.items()}


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
