"""Model class `xing4_0`: how a Xing4.0-shaped configuration file (the
source's own HF keys) becomes the program's module —
`models/latent_moe.py`'s stack with a residual stream of `hc_mult` lanes
mixed by manifold-constrained hyper-connections
(`models/hyper_connection.py`), latent attention with a q latent and
YaRN-scaled rotation (`models/latent_attention.py`, `models/llama.py::
RopeScaling`), leading dense SwiGLU layers, sigmoid-routed SwiGLU expert
layers with a shared expert (`models/moe.py`) and, where the file keeps
it, a multi-token-prediction module — its plain reference
(`reference_xing4_0.py`), and its operation and byte counts.

The file's `n_routed_experts` is how many experts are HELD (a chip's
share); the router's width is `share.n_routed_experts_published`.  What
the held rows and the kernels' costs are is `kimi_vl.py`'s, which reads
the same keys; what differs is here: the products a token passes through
(the q latent's two, a hyper-connection's), the bytes the mixing must
move, what `build` refuses, and what the seeded state draws where the
program's init is a constant (`seeded_leaves`).
"""

from __future__ import annotations

import functools
import math
import zlib

from benchmark import reference_xing4_0
from benchmark.models import kimi_vl

attention_pairs_flops_per_token = kimi_vl.attention_pairs_flops_per_token
attention_cost_per_step = kimi_vl.attention_cost_per_step
moe_cost_per_step = kimi_vl.moe_cost_per_step


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.latent_moe import (
        LatentMoE,
        LatentMoEConfig,
    )
    from dlrover_wuqiong_tpu.models.llama import RopeScaling

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
    if config["attention_bias"]:
        raise ValueError("the program's projections have no bias")
    if config["tie_word_embeddings"]:
        raise ValueError("the program's head is untied")
    scaling = config["rope_scaling"]
    if scaling is not None and scaling["type"] != "yarn":
        raise ValueError("the program scales the rotation by YaRN or not "
                         "at all")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention up-projects one key and one "
                         "value head a query head")
    if not 0 < config["first_k_dense_replace"] <= config["num_hidden_layers"]:
        raise ValueError("the leading dense layers lie inside the depth")
    if config["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("one multi-token-prediction module or none")
    if config["train"]["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("the sequence is longer than the positions")
    prog, share, train = config["program"], config["share"], config["train"]
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
        q_lora_rank=config["q_lora_rank"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=float(config["rope_theta"]),
        rope_scaling=scaling and RopeScaling(
            factor=scaling["factor"],
            original_max_position_embeddings=scaling[
                "original_max_position_embeddings"],
            beta_fast=scaling["beta_fast"], beta_slow=scaling["beta_slow"],
            mscale=scaling["mscale"],
            mscale_all_dim=scaling["mscale_all_dim"]),
        rms_eps=config["rms_norm_eps"],
        residual_lanes=config["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_res_clamp=(float(config["mhc_h_res_clamp_min"]),
                      float(config["mhc_h_res_clamp_max"])),
        mtp_layers=config["num_nextn_predict_layers"],
        mtp_loss_weight=train["mtp_loss_weight"],
        num_experts=share["n_routed_experts_published"],
        top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_experts=config["n_shared_experts"],
        routed_scaling=config["routed_scaling_factor"],
        experts_held=config["n_routed_experts"],
        first_expert=share["first_expert"],
        bias_update_rate=train["selection_bias_update_rate"],
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


# What the seeded state draws where the program's init is a constant (the
# file's `assumed.seeded_state` gives the reason for each): the standard
# deviation of the DYNAMIC part of a mixing logit, alpha (z Phi) with z of
# unit RMS; of what is added to b_pre, b_post and b_res; the tilt of
# b_post's lanes and b_res's rows, x linspace(1, -1, lanes); the RMS of
# an entry of the embedding table; the factor on the q latent's
# down-projection.
SEEDED_LOGIT_STD = 0.48
SEEDED_BIAS_STD = 0.5
SEEDED_TILT = 1.5
SEEDED_EMBEDDING_RMS = 0.3
SEEDED_Q_LATENT_SCALE = 2.0


def seeded_leaves(params, key):
    """`params` with the leaves the program's init holds constant drawn
    from `key`, so that the two scalars the check compares depend on
    every equation this class adds.  Every hyper-connection's Phi normal,
    at the width that gives a logit's dynamic part `SEEDED_LOGIT_STD`
    under the module's own gains: the coefficients differ token by
    token.  Its three biases the init's plus normal(`SEEDED_BIAS_STD`),
    b_post's lanes and b_res's ROWS tilted besides: the lanes differ in
    size from the first sublayer on, and a row tilt is what ONE Sinkhorn
    round (columns first, rows last) cannot undo — its column sums lie
    far from 1 and weigh the largest lanes least.  The embedding at an
    entry's RMS of `SEEDED_EMBEDDING_RMS`, beside the branches' outputs
    and not a sixtieth of them: h_post's factor is no common scale.  The
    q latent's down-projection times `SEEDED_Q_LATENT_SCALE`: the
    latent's RMS is not the 1 its norm would leave alone.  One fold of
    the key a hyper-connection, by its path."""
    import jax
    import jax.numpy as jnp

    def mixing(leaves, key):
        k_phi, k_pre, k_post, k_res = jax.random.split(key, 4)
        n, d, _ = leaves["phi"].shape
        gains = jnp.repeat(leaves["alpha"], jnp.array([n, n, n * n]),
                           total_repeat_length=n * (n + 2))
        tilt = SEEDED_TILT * jnp.linspace(1.0, -1.0, n)
        drawn = {"phi": jax.random.normal(k_phi, leaves["phi"].shape)
                 * SEEDED_LOGIT_STD / (gains * math.sqrt(n * d))}
        for name, k, by_lane in (("b_pre", k_pre, 0.0),
                                 ("b_post", k_post, tilt),
                                 ("b_res", k_res, tilt[:, None])):
            drawn[name] = leaves[name] + by_lane + SEEDED_BIAS_STD \
                * jax.random.normal(k, leaves[name].shape)
        return {**leaves, **drawn}

    def walk(tree, path):
        if not isinstance(tree, dict):
            return tree
        out = {name: walk(sub, (*path, name)) for name, sub in tree.items()}
        module = path[-1] if path else ""
        if module.endswith("_hc"):
            out = mixing(out, jax.random.fold_in(
                key, zlib.crc32("/".join(path).encode())))
        elif module == "embed_tokens":
            table = out["embedding"]
            out = {**out, "embedding": table * SEEDED_EMBEDDING_RMS
                   * math.sqrt(table.shape[-1])}
        elif module == "q_a_proj":
            out = {**out, "kernel": out["kernel"] * SEEDED_Q_LATENT_SCALE}
        return out

    return walk(params, ())


def seeded_state(trainer, seed: int):
    """`kimi_vl.seeded_state` — every leaf from `seed`, each expert
    layer's selection bias balanced on the seed's first batch — over a
    draw that ends in `seeded_leaves`: the one jitted draw that
    `gpt.seeded_state` keeps on the trainer is made here, before it
    looks for one, so the balancing sees the leaves the step will."""
    import jax

    from dlrover_wuqiong_tpu.trainer.train_step import TrainState

    if getattr(trainer, "_bench_seeded_init", None) is None:
        model, optimizer = trainer.res.model, trainer.optimizer
        trainer._bench_seeded_init = jax.jit(
            lambda key: TrainState.create(
                seeded_leaves(model.init_params(key), key), optimizer),
            out_shardings=trainer.res.state_shardings)
    return kimi_vl.seeded_state(trainer, seed)


def reference_loss(config: dict, **over):
    """`loss(params, batch)` of the plain reference for this file;
    `over` replaces a size or sets a control (a dtype, a wrong
    equation)."""
    return functools.partial(
        reference_xing4_0.loss, **{**dict(
            n_layer=config["num_hidden_layers"],
            first_dense=config["first_k_dense_replace"],
            n_head=config["num_attention_heads"],
            nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
            top_k=config["num_experts_per_tok"],
            routed_scaling=config["routed_scaling_factor"],
            first_expert=config["share"]["first_expert"],
            eps=config["rms_norm_eps"], theta=float(config["rope_theta"]),
            yarn=config["rope_scaling"], lanes=config["hc_mult"],
            sinkhorn_iters=config["hc_sinkhorn_iters"],
            hc_eps=config["hc_eps"],
            res_clamp=(config["mhc_h_res_clamp_min"],
                       config["mhc_h_res_clamp_max"]),
            mtp=config["num_nextn_predict_layers"],
            mtp_weight=config["train"]["mtp_loss_weight"]), **over})


def _sublayers(config: dict) -> int:
    """Hyper-connections: two a block."""
    return 2 * config["num_hidden_layers"]


def dense_params_per_token(config: dict) -> dict:
    """Matmul parameters (multiply-adds) one token passes through, by
    part: latent attention's five products (the q latent's two, the
    down-projection to latent and rope key, the up-projection from the
    latent, o), the leading dense SwiGLUs, the expert layers as
    `kimi_vl.py` counts them, the untied head, and `mixing`: a
    hyper-connection's (n d) x (n^2 + 2n) product and the two mixes'
    n + n^2 + n multiply-adds a hidden feature.  No MTP module is
    counted: the cell runs without one."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    kimi = kimi_vl.dense_params_per_token(config)
    q_latent = config["q_lora_rank"] * (h + heads * qk)
    n = config["hc_mult"]
    return {**kimi,
            "attention": kimi["attention"] + config["num_hidden_layers"]
            * (q_latent - h * heads * qk),
            "mixing": _sublayers(config) * (n * h + h) * (n * n + 2 * n)}


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires, as `kimi_vl.py`
    counts them over this file's products.  Norms, RoPE, gates and
    Sinkhorn are left out; recomputation is not counted."""
    return 6.0 * sum(dense_params_per_token(config).values()) \
        + 3.0 * attention_pairs_flops_per_token(config)


def resmix_bytes_per_step(config: dict, global_batch: int,
                          bytes_per_el: int = 2) -> int:
    """The LEAST HBM bytes any implementation moves for the residual
    mixing of one optimizer step, from the configuration and the batch
    alone: a sublayer reads each of the n lanes once for the pre-mix,
    reads each once and writes each once for the post/residual mix,
    writes the branch's input and reads its output — (3 n + 2) hidden
    vectors a token — forward, once more in the recomputed forward, and
    the backward at twice the forward.  Coefficients are not counted."""
    per_token = (3 * config["hc_mult"] + 2) * config["hidden_size"] \
        * bytes_per_el
    tokens = global_batch * config["train"]["seq_len"]
    return 4 * _sublayers(config) * tokens * per_token
