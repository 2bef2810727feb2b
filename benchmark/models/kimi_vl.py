"""Model class `kimi_vl`: how a Kimi-VL-shaped configuration file (the
source's own HF keys, the LANGUAGE model's) becomes the program's module
— `models/latent_moe.py`'s stack of latent attention
(`models/latent_attention.py`, `ops/flash_attention.py` at two widths), a
leading dense SwiGLU layer and sigmoid-routed SwiGLU expert layers with
a shared expert (`models/moe.py`) — its plain reference
(`reference_kimi_vl.py`), and its operation counts.

The file's `n_routed_experts` is how many experts are HELD (a chip's
share); the router's width is `share.n_routed_experts_published`.
"""

from __future__ import annotations

import functools

from benchmark import reference_kimi_vl
from benchmark.models import gpt, nemotron_h

# as `nemotron_h.py`: rounds of (one forward pass, one solve a layer), a
# later layer's scores moving with the routing of the layers before it
_BALANCE_ROUNDS = 3


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.latent_moe import (
        LatentMoE,
        LatentMoEConfig,
    )

    if (config["hidden_act"], config["scoring_func"],
            config["topk_method"]) != ("silu", "sigmoid", "noaux_tc"):
        raise ValueError("the program's experts are SwiGLU, its router a "
                         "sigmoid with a selection bias")
    if (config["n_group"], config["topk_group"]) != (1, 1) \
            or not config["norm_topk_prob"]:
        raise ValueError("the program's router has no group limit and "
                         "normalises the chosen gates")
    if config["moe_layer_freq"] != 1:
        raise ValueError("every layer behind the dense ones is an expert "
                         "layer")
    if config["rope_scaling"] is not None or config["attention_bias"]:
        raise ValueError("the program's RoPE is unscaled and its "
                         "projections have no bias")
    if config["tie_word_embeddings"]:
        raise ValueError("the program's head is untied")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention up-projects one key and one "
                         "value head a query head")
    if not 0 < config["first_k_dense_replace"] <= config["num_hidden_layers"]:
        raise ValueError("the leading dense layers lie inside the depth")
    if config["train"]["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("the sequence is longer than the positions")
    prog, share = config["program"], config["share"]
    if prog["impl"] != "grouped":
        raise ValueError("a share of the experts exists in the grouped "
                         "path only")
    return LatentMoE(LatentMoEConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        first_dense_layers=config["first_k_dense_replace"],
        dense_width=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        kv_lora_rank=config["kv_lora_rank"],
        q_lora_rank=config["q_lora_rank"],  # LatentAttention refuses one
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        num_experts=share["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_experts=config["n_shared_experts"],
        routed_scaling=config["routed_scaling_factor"],
        experts_held=config["n_routed_experts"],
        first_expert=share["first_expert"],
        bias_update_rate=config["train"]["selection_bias_update_rate"],
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def _expert_layers(params) -> list:
    return sorted((name for name, layer in params.items()
                   if "selection_bias" in layer.get("feed_forward", ())),
                  key=lambda name: int(name.rsplit("_", 1)[1]))


def seeded_state(trainer, seed: int):
    """The train state drawn from `seed` as every model class's is
    (`models/gpt.py::seeded_state`), and then each expert layer's
    selection bias set to where the out-of-band rule settles on the
    seed's first batch (`nemotron_h.balanced_bias`): every one of the
    published experts is chosen equally often there, as in the
    deployment, whose bias the rule has balanced."""
    import jax
    import numpy as np

    state = gpt.seeded_state(trainer, seed)
    model = trainer.res.model
    top_k = model.config.top_k
    layers = _expert_layers(state.params)
    done = getattr(trainer, "_bench_balanced", None)
    if done is None or done[0] != seed:
        one_round = getattr(trainer, "_bench_balance_round", None)
        if one_round is None:  # traced once a process

            def one_round(params, ids):
                _, found = model.apply(
                    {"params": params}, ids, mutable=["intermediates"],
                    capture_intermediates=lambda m, _: m.name == "router")
                found = found["intermediates"]
                return {name: nemotron_h.balanced_bias(jax.nn.sigmoid(
                    found[name]["feed_forward"]["router"]["__call__"][0]),
                    top_k) for name in layers}

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
    `over` replaces a size or sets a control (a dtype, a wrong
    equation)."""
    return functools.partial(
        reference_kimi_vl.loss, **{**dict(
            n_layer=config["num_hidden_layers"],
            first_dense=config["first_k_dense_replace"],
            n_head=config["num_attention_heads"],
            nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
            top_k=config["num_experts_per_tok"],
            routed_scaling=config["routed_scaling_factor"],
            first_expert=config["share"]["first_expert"],
            eps=config["rms_norm_eps"],
            theta=float(config["rope_theta"])), **over})


def _held_rows_per_token(config: dict) -> float:
    """Rows a token sends to the experts held here, EXPECTED under even
    routing: its choices fall on the held experts of the published count
    with that share (6 x 8 / 64 = 0.75 at the cell's)."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] \
        / config["share"]["n_routed_experts_published"]


def _layers(config: dict) -> tuple:
    """(leading dense layers, expert layers)."""
    dense = config["first_k_dense_replace"]
    return dense, config["num_hidden_layers"] - dense


def _head_lanes(config: dict) -> tuple:
    """(q's and k's lanes a head, v's)."""
    return (config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            config["v_head_dim"])


def dense_params_per_token(config: dict) -> dict:
    """Matmul parameters one token passes through, by part: the latent
    attention's four products (q, the down-projection to latent and
    rope key, the up-projection from the latent, o), the leading dense
    SwiGLUs, the expert layers (router over all published experts,
    shared expert, ROUTED experts at the expected rows a token sends to
    the experts held here), the untied head; the embedding is a lookup."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    qk, v = _head_lanes(config)
    rank = config["kv_lora_rank"]
    dense, expert = _layers(config)
    f = config["moe_intermediate_size"]
    attn = h * heads * qk + h * (rank + config["qk_rope_head_dim"]) \
        + rank * heads * (config["qk_nope_head_dim"] + v) + heads * v * h
    return {
        "attention": config["num_hidden_layers"] * attn,
        "dense": dense * 3 * h * config["intermediate_size"],
        "router": expert * h * config["share"]["n_routed_experts_published"],
        "shared": expert * 3 * h * config["n_shared_experts"] * f,
        "routed": expert * _held_rows_per_token(config) * 3 * h * f,
        "head": h * config["vocab_size"]}


def attention_pairs_flops_per_token(config: dict) -> float:
    """FORWARD FLOPs a token of the attention kernels' two products, all
    layers: 2 x (192 + 128) a kept (query, key) pair of every head, the
    causal triangle's seq x (seq + 1) / 2 pairs a sequence."""
    seq = config["train"]["seq_len"]
    return 2.0 * sum(_head_lanes(config)) * config["num_attention_heads"] \
        * config["num_hidden_layers"] * (seq + 1) / 2


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through (`dense_params_per_token`) plus three
    times the attention products' forward FLOPs: S over 192 lanes and O
    over 128 forward; dQ and dK over 192, dP and dV over 128 backward.
    Norms, RoPE and gates are left out; recomputation is not counted."""
    return 6.0 * sum(dense_params_per_token(config).values()) \
        + 3.0 * attention_pairs_flops_per_token(config)


def attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """Attention FLOPs and least HBM bytes of one optimizer step over
    the whole batch, all layers, on kept pairs (no recomputation counted:
    a flash backward's recomputed scores are its own remat).  S, dQ and
    dK contract or produce 192 lanes a pair, O, dV and dP 128.  Bytes:
    forward reads q and k (192 wide) and v (128) and writes o (128);
    backward reads q, k, v, o and dO and writes dq, dk (192) and dv
    (128): q, k, dq, dk at 192 and v, o, dO, dv at 128, bf16, a head."""
    seq, heads = config["train"]["seq_len"], config["num_attention_heads"]
    qk, v = _head_lanes(config)
    layers = config["num_hidden_layers"]
    pairs = seq * (seq + 1) // 2 * global_batch * heads * layers
    rows = global_batch * heads * seq * layers * 2  # bytes of one lane
    return {"flops_fwd": 2 * (qk + v) * pairs,
            "flops_bwd": 2 * (2 * qk + 2 * v) * pairs,
            "flops": 6 * (qk + v) * pairs,
            "bytes_fwd": (2 * qk + 2 * v) * rows,
            "bytes_bwd": (4 * qk + 4 * v) * rows,
            "bytes": (6 * qk + 6 * v) * rows}


def moe_cost_per_step(config: dict, global_batch: int,
                      bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the HELD experts' matmuls of one
    optimizer step, forward + backward, all expert layers, at the
    expected rows (`_held_rows_per_token`): what the ROUTING asks for.

    Each row passes three (hidden x width) matrices (SwiGLU: gate, up,
    down): 2*hidden*width FLOPs each forward, twice that backward.  The
    router, the top-k, the sort, the row gathers and the shared expert
    are NOT in it.  Bytes as `models/olmoe.py` counts them for a fused
    pass: forward reads the rows and the three weight tensors and writes
    the output rows; backward reads the rows, the output's gradient and
    the weights, and writes the rows' gradient and the three weight
    gradients."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    rows = int(global_batch * config["train"]["seq_len"]
               * _held_rows_per_token(config))
    per_matmul = 2 * rows * h * f
    row_bytes = rows * h * bytes_per_el
    weight_bytes = 3 * config["n_routed_experts"] * h * f * bytes_per_el
    one = {"flops_fwd": 3 * per_matmul, "flops_bwd": 6 * per_matmul,
           "flops": 9 * per_matmul,
           "bytes_fwd": 2 * row_bytes + weight_bytes,
           "bytes_bwd": 3 * row_bytes + 2 * weight_bytes,
           "bytes": 5 * row_bytes + 3 * weight_bytes}
    return {k: v * _layers(config)[1] for k, v in one.items()}
