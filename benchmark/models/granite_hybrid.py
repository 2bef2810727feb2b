"""Model class `granite_hybrid`: how a `granitemoehybrid`-shaped
configuration file WITHOUT experts (the source's own HF keys) becomes the
program's module — `models/granite_hybrid.py`'s stack of blocks of two
sublayers, a Mamba-2 mixer (`models/mamba2.py`, `ops/ssd.py`) or a
no-position grouped-query attention (`models/llama.py`) and a dense
SwiGLU, under four scalar multipliers and a tied head — its plain
reference (`reference_granite_hybrid.py`), and its operation counts.
"""

from __future__ import annotations

import functools

from benchmark import reference_granite_hybrid
from benchmark.models import gpt, nemotron_h

seeded_state = gpt.seeded_state  # the draw every model class makes


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.granite_hybrid import (
        GraniteHybrid,
        GraniteHybridConfig,
    )

    if config["model_type"] != "granitemoehybrid":
        raise ValueError("not a granitemoehybrid configuration")
    if config["num_local_experts"] or config["num_experts_per_tok"]:
        raise ValueError("the program's block has a dense feed-forward and "
                         "no experts")
    if config["shared_intermediate_size"] != config["intermediate_size"]:
        raise ValueError("one feed-forward width")
    if (config["hidden_act"], config["normalization_function"]) != \
            ("silu", "rmsnorm"):
        raise ValueError("the program's feed-forward is a SwiGLU and its "
                         "norms RMSNorm only")
    if config["attention_bias"] or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"]:
        raise ValueError("the program's projections have no bias and its "
                         "convolution has one")
    if config["position_embedding_type"] != "nope":
        raise ValueError("the program's attention rotates nothing here")
    if not config["tie_word_embeddings"]:
        raise ValueError("the program's head is the embedding")
    if config["mamba_n_heads"] % config["mamba_n_groups"]:
        raise ValueError("the Mamba-2 heads do not divide into the groups")
    if config["mamba_n_heads"] * config["mamba_d_head"] != \
            config["mamba_expand"] * config["hidden_size"]:
        raise ValueError("heads x head size is not expand x hidden")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types has one entry a layer")
    prog = config["program"]
    if config["train"]["seq_len"] > config["max_position_embeddings"] \
            or config["train"]["seq_len"] % prog["mamba_chunk_size"]:
        raise ValueError("the sequence is longer than the positions, or "
                         "no multiple of the scan's chunk")
    return GraniteHybrid(GraniteHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        max_seq_len=config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"],
        intermediate_size=config["shared_intermediate_size"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        mamba_heads=config["mamba_n_heads"],
        mamba_head_dim=config["mamba_d_head"],
        n_groups=config["mamba_n_groups"],
        state_size=config["mamba_d_state"],
        conv_kernel=config["mamba_d_conv"],
        chunk_size=prog["mamba_chunk_size"],
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def reference_loss(config: dict):
    """`loss(params, batch)` of the plain reference for this file."""
    return functools.partial(
        reference_granite_hybrid.loss,
        layer_types=tuple(config["layer_types"]),
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        mamba_heads=config["mamba_n_heads"],
        mamba_head_dim=config["mamba_d_head"],
        n_groups=config["mamba_n_groups"], state=config["mamba_d_state"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
        eps=config["rms_norm_eps"])


def _in_nemotron_keys(config: dict) -> dict:
    """This file's shapes under the keys `nemotron_h.py`'s counting rules
    read, so that both hybrids' scans and attention layers are counted
    by ONE rule."""
    return {
        "hybrid_override_pattern": "".join(
            "M" if kind == "mamba" else "*" for kind in config["layer_types"]),
        "mamba_num_heads": config["mamba_n_heads"],
        "mamba_head_dim": config["mamba_d_head"],
        "ssm_state_size": config["mamba_d_state"],
        "n_groups": config["mamba_n_groups"],
        "num_attention_heads": config["num_attention_heads"],
        "num_key_value_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "train": config["train"]}


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through — every block's SwiGLU, a `mamba`
    layer's two projections, an `attention` layer's four, the tied head
    (the embedding is a lookup) — plus causal attention's
    6*T*heads*head_dim an attention layer and three times the
    recurrence's forward operations a `mamba` layer
    (`nemotron_h._scan_flops_per_token`: 6*P*N a head).  The convolution,
    norms, gates and the four multipliers are left out; recomputation is
    not counted."""
    h, kinds = config["hidden_size"], config["layer_types"]
    n_mamba, n_attn = kinds.count("mamba"), kinds.count("attention")
    d_inner = config["mamba_n_heads"] * config["mamba_d_head"]
    in_proj = 2 * d_inner + 2 * config["mamba_n_groups"] \
        * config["mamba_d_state"] + config["mamba_n_heads"]
    mamba = h * in_proj + d_inner * h
    q = h  # heads x head size is the hidden size
    kv = q * config["num_key_value_heads"] // config["num_attention_heads"]
    attn = 2 * h * q + 2 * h * kv
    mlp = 3 * h * config["shared_intermediate_size"]
    params = n_mamba * mamba + n_attn * attn + len(kinds) * mlp \
        + h * config["vocab_size"]
    return 6.0 * params + 6.0 * n_attn * config["train"]["seq_len"] * q \
        + 3.0 * n_mamba * nemotron_h._scan_flops_per_token(
            _in_nemotron_keys(config))


def attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """Causal attention FLOPs and bytes of one optimizer step over the
    whole batch, all attention layers, as `nemotron_h.py` counts them:
    FLOPs of every query head; of the bytes, k, v and their gradients
    once a KEY/VALUE head, q, o and theirs once a query head."""
    return nemotron_h.attention_cost_per_step(_in_nemotron_keys(config),
                                              global_batch)


def ssd_cost_per_step(config: dict, global_batch: int,
                      bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the state-space scan of one optimizer
    step, forward + backward, all `mamba` layers, by `nemotron_h.py`'s
    rule: the RECURRENCE's 6*P*N operations a head a token forward, twice
    that backward, so that no choice of chunk moves the count; x, B, C,
    the step size and y moved once each way.  Both err low."""
    return nemotron_h.ssd_cost_per_step(_in_nemotron_keys(config),
                                        global_batch, bytes_per_el)
