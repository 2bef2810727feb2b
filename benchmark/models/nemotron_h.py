"""Model class `nemotron_h`: how a Nemotron-H-shaped configuration file
(the source's own HF keys) becomes the program's module —
`models/nemotron_h.py`'s pattern-driven stack of Mamba-2 mixers
(`models/mamba2.py`, `ops/ssd.py`), expert layers (`models/moe.py`) and
no-RoPE grouped-query attention (`models/llama.py`) — its plain
reference (`reference_nemotron_h.py`), and its operation counts.

The file's `n_routed_experts` is how many experts are HELD (a chip's
share); the router's width is `share.n_routed_experts_published`.
"""

from __future__ import annotations

import functools

from benchmark import flops, reference_nemotron_h
from benchmark.models import gpt

# the selection biases are balanced on the seed's first batch: rounds of
# (one forward pass, one solve a layer), a later layer's scores moving
# with the routing of the layers before it
_BALANCE_ROUNDS = 3
_SOLVE_ITERS = 24


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.nemotron_h import (
        NemotronH,
        NemotronHConfig,
    )

    if config["model_type"] != "nemotron_h":
        raise ValueError("not a nemotron_h configuration")
    if (config["mlp_hidden_act"], config["mamba_hidden_act"]) != \
            ("relu2", "silu"):
        raise ValueError("the program's experts are relu2 and its Mamba "
                         "mixer silu only")
    if any(config[k] for k in ("attention_bias", "mlp_bias", "use_bias",
                               "mamba_proj_bias")) \
            or not config["use_conv_bias"]:
        raise ValueError("the program's projections have no bias and its "
                         "convolution has one")
    if (config["n_group"], config["topk_group"]) != (1, 1):
        raise ValueError("the program's router has no group limit")
    if config["n_shared_experts"] != 1 or config["tie_word_embeddings"]:
        raise ValueError("the program runs one shared expert and an untied "
                         "head")
    if config["sliding_window"] is not None or config["residual_in_fp32"]:
        raise ValueError("the program's attention is full and its residual "
                         "stream in the compute dtype")
    if config["moe_intermediate_size"] != config["intermediate_size"]:
        raise ValueError("one expert width")
    if len(config["hybrid_override_pattern"]) != config["num_hidden_layers"]:
        raise ValueError("the pattern has one character a layer")
    if config["train"]["seq_len"] > config["max_position_embeddings"] \
            or config["train"]["seq_len"] % config["chunk_size"]:
        raise ValueError("the sequence is longer than the positions, or "
                         "no multiple of the scan's chunk")
    prog, share = config["program"], config["share"]
    if prog["impl"] != "grouped":
        raise ValueError("a share of the experts exists in the grouped "
                         "path only")
    return NemotronH(NemotronHConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        pattern=config["hybrid_override_pattern"],
        max_seq_len=config["max_position_embeddings"],
        rms_eps=config["layer_norm_epsilon"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        n_groups=config["n_groups"], state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        dt_min=config["time_step_min"], dt_max=config["time_step_max"],
        dt_floor=config["time_step_floor"],
        num_experts=share["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["moe_shared_expert_intermediate_size"],
        routed_scaling=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        experts_held=config["n_routed_experts"],
        first_expert=share["first_expert"],
        bias_update_rate=config["train"]["selection_bias_update_rate"],
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def balanced_bias(scores, top_k: int, iters: int = _SOLVE_ITERS):
    """A selection bias (E,), mean 0, under which every expert is among
    the `top_k` of `scores + bias` for the same number of the T tokens
    (T * top_k / E, to within the ties): what the out-of-band rule
    settles at on these scores.  Each pass holds every token's threshold
    (its k-th largest biased score) and moves each expert's bias half of
    the way to where T * top_k / E tokens lie over their thresholds."""
    import jax
    import jax.numpy as jnp

    n_tok, n_exp = scores.shape
    want = n_tok * top_k // n_exp

    def one_pass(bias, _):
        kth = jax.lax.top_k(scores + bias, top_k)[0][:, -1]
        over = jnp.sort(scores - kth[:, None], axis=0)  # ascending
        # between the want-th and the (want + 1)-th largest margin
        level = -(over[n_tok - want] + over[n_tok - want - 1]) / 2
        bias = bias + 0.5 * (level - bias)
        return bias - bias.mean(), ()

    return jax.lax.scan(one_pass, jnp.zeros((n_exp,), scores.dtype), (),
                        length=iters)[0]


def _expert_layers(params) -> list:
    return sorted((name for name, layer in params.items()
                   if "feed_forward" in layer),
                  key=lambda name: int(name.rsplit("_", 1)[1]))


def _with_biases(params, biases: dict):
    """`params` with each named layer's `selection_bias` replaced."""
    import jax
    import jax.numpy as jnp

    out = dict(params)
    for name, bias in biases.items():
        old = params[name]["feed_forward"]["selection_bias"]
        out[name] = {**params[name], "feed_forward": {
            **params[name]["feed_forward"],
            "selection_bias": jax.device_put(
                jnp.asarray(bias, old.dtype), old.sharding)}}
    return out


def seeded_state(trainer, seed: int):
    """The train state drawn from `seed` as every model class's is
    (`models/gpt.py::seeded_state`), and then each expert layer's
    selection bias set to where the out-of-band rule settles on the
    seed's first batch (`balanced_bias`): every one of the published
    experts is chosen equally often there, as in the deployment, whose
    bias the rule has balanced.  At the normal(0.02) draw 8 of 128
    experts see 4.6% to 11.9% of a fresh model's assignments, by seed
    (PERF.md section 6, PR 31), and a run's work with them."""
    import jax
    import numpy as np

    state = gpt.seeded_state(trainer, seed)
    model = trainer.res.model
    top_k = model.config.top_k
    layers = _expert_layers(state.params)
    if not layers:
        return state
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
                    top_k) for name in layers}

            one_round = trainer._bench_balance_round = jax.jit(one_round)
        data = getattr(trainer.train_data, "inner", trainer.train_data)
        ids = trainer.res.place_batch(dict(data(0)))["input_ids"]
        params, biases = state.params, {}
        for _ in range(_BALANCE_ROUNDS):
            biases = one_round(params, ids)
            params = _with_biases(state.params, biases)
        # on the host: the step donates whatever the state holds
        trainer._bench_balanced = done = (
            seed, {name: np.asarray(b) for name, b in biases.items()})
    state = state._replace(params=_with_biases(state.params, done[1]))
    trainer.res.state = state
    return state


def reference_loss(config: dict):
    """`loss(params, batch)` of the plain reference for this file."""
    return functools.partial(
        reference_nemotron_h.loss,
        pattern=config["hybrid_override_pattern"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"], mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        n_groups=config["n_groups"], state=config["ssm_state_size"],
        top_k=config["num_experts_per_tok"],
        routed_scaling=config["routed_scaling_factor"],
        first_expert=config["share"]["first_expert"],
        eps=config["layer_norm_epsilon"])


def _layers(config: dict) -> dict:
    pattern = config["hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in "ME*"}


def _held_rows_per_token(config: dict) -> float:
    """Rows a token sends to the experts held here, EXPECTED under even
    routing: its `num_experts_per_tok` choices fall on the held
    `n_routed_experts` of the published count with that share."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] \
        / config["share"]["n_routed_experts_published"]


def _scan_flops_per_token(config: dict) -> int:
    """The RECURRENCE's operations of one Mamba-2 layer for one token,
    forward, as its equation is written: per head the decay of S (P*N
    products), x (x) B (P*N), times the step size (P*N), their sum (P*N)
    and y = S C (2*P*N): 6*P*N a head.  What a chunked form spends
    beyond that (the masked L x L products) is that form's own cost, not
    counted."""
    return 6 * config["mamba_head_dim"] * config["ssm_state_size"] \
        * config["mamba_num_heads"]


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through — a Mamba-2 layer's two projections, an
    attention layer's four, an expert layer's router over all published
    experts, its shared expert, and its ROUTED experts counted at the
    EXPECTED rows a token sends to the experts held here (6 x 8 / 128 =
    0.375 at the cell's share: what a run really routes there is
    `moe.held_rows_share`), the untied head; the embedding is a lookup —
    plus causal attention's 6*T*heads*head_dim a layer and three times
    the recurrence's forward operations a Mamba-2 layer
    (`_scan_flops_per_token`).  The convolution, norms and gates are left
    out; recomputation is not counted."""
    h, n = config["hidden_size"], _layers(config)
    d_inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    in_proj = 2 * d_inner + 2 * config["n_groups"] \
        * config["ssm_state_size"] + config["mamba_num_heads"]
    mamba = h * in_proj + d_inner * h
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    attn = 2 * h * q + 2 * h * kv
    f = config["moe_intermediate_size"]
    expert = h * config["share"]["n_routed_experts_published"] \
        + 2 * h * config["moe_shared_expert_intermediate_size"] \
        + _held_rows_per_token(config) * 2 * h * f
    params = n["M"] * mamba + n["*"] * attn + n["E"] * expert \
        + h * config["vocab_size"]
    return 6.0 * params + 6.0 * n["*"] * config["train"]["seq_len"] * q \
        + 3.0 * n["M"] * _scan_flops_per_token(config)


def attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """Causal attention FLOPs and bytes of one optimizer step over the
    whole batch, all attention layers.  FLOPs are those of every query
    head; of the bytes, k, v and their gradients are counted once a
    KEY/VALUE head (a kernel that reads them once a group moves no
    more), q, o and theirs once a query head."""
    seq, d = config["train"]["seq_len"], config["head_dim"]
    per_q = flops.causal_attention_cost(
        global_batch, config["num_attention_heads"], seq, d, bytes_per_el=2)
    per_kv = flops.causal_attention_cost(
        global_batch, config["num_key_value_heads"], seq, d, bytes_per_el=2)
    one = {k: v for k, v in per_q.items() if k.startswith("flops")}
    for k in ("bytes_fwd", "bytes_bwd", "bytes"):
        one[k] = (per_q[k] + per_kv[k]) // 2  # half the tensors are k, v
    return {k: v * _layers(config)["*"] for k, v in one.items()}


def moe_cost_per_step(config: dict, global_batch: int,
                      bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the HELD experts' matmuls of one
    optimizer step, forward + backward, all expert layers, at the
    expected rows (`_held_rows_per_token`): what the ROUTING asks for.
    The program's grouped kernels run over the static T*k-row buffer of
    which the held rows are the first 6% or so (`moe.held_rows_share`),
    so `kernel.moe_gmm_roofline` reads how much of their time the routed
    work would need at the roofline.

    Each row passes two (hidden x width) matrices (relu2 has no gate):
    2*hidden*width FLOPs each forward, twice that backward.  The router,
    the top-k, the sort, the gather, the scatter-add and the shared
    expert are NOT in it.  Bytes as `models/olmoe.py` counts them for a
    fused pass: forward reads the rows and the two weight tensors and
    writes the output rows; backward reads the rows, the output's
    gradient and the weights, and writes the rows' gradient and the two
    weight gradients."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    rows = int(global_batch * config["train"]["seq_len"]
               * _held_rows_per_token(config))
    per_matmul = 2 * rows * h * f
    row_bytes = rows * h * bytes_per_el
    weight_bytes = 2 * config["n_routed_experts"] * h * f * bytes_per_el
    one = {"flops_fwd": 2 * per_matmul, "flops_bwd": 4 * per_matmul,
           "flops": 6 * per_matmul,
           "bytes_fwd": 2 * row_bytes + weight_bytes,
           "bytes_bwd": 3 * row_bytes + 2 * weight_bytes,
           "bytes": 5 * row_bytes + 3 * weight_bytes}
    return {k: v * _layers(config)["E"] for k, v in one.items()}


def ssd_cost_per_step(config: dict, global_batch: int,
                      bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the state-space scan of one optimizer
    step, forward + backward, all Mamba-2 layers.

    FLOPs: the RECURRENCE's (`_scan_flops_per_token`), twice that
    backward — not the chunked form's, so that no choice of chunk size
    moves the count.  Bytes: each of x (H*P), B and C (G*N each), the
    step size (H) and y (H*P) read or written once forward, and once
    more backward (their gradients), at `bytes_per_el`; a state that
    never leaves the chip's fast memory.  Both err low: the share of the
    roofline this gives cannot pass 100% by a later change of form."""
    tokens = global_batch * config["train"]["seq_len"]
    hp = config["mamba_num_heads"] * config["mamba_head_dim"]
    gn = config["n_groups"] * config["ssm_state_size"]
    one_way = tokens * (2 * hp + 2 * gn + config["mamba_num_heads"]) \
        * bytes_per_el
    fwd = tokens * _scan_flops_per_token(config)
    one = {"flops_fwd": fwd, "flops_bwd": 2 * fwd, "flops": 3 * fwd,
           "bytes_fwd": one_way, "bytes_bwd": one_way, "bytes": 2 * one_way}
    return {k: v * _layers(config)["M"] for k, v in one.items()}
