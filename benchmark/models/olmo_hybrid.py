"""Model class `olmo_hybrid`: how an `olmo_hybrid`-shaped configuration
file (the source's own HF keys) becomes the program's module —
`models/olmo_hybrid.py`'s stack of blocks of two sublayers under the
reordered norm, a gated delta-rule mixer (`models/gated_delta.py`,
`ops/delta_rule.py`) or a QK-normed attention without rotation
(`models/llama.py`) and a dense SwiGLU, an untied head — its plain
reference (`reference_olmo_hybrid.py`), and its operation counts.
"""

from __future__ import annotations

import functools

from benchmark import flops, reference_olmo_hybrid
from benchmark.models import gpt

seeded_state = gpt.seeded_state  # the draw every model class makes

KINDS = ("linear_attention", "full_attention")


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.olmo_hybrid import (
        OlmoHybrid,
        OlmoHybridConfig,
    )

    if config["model_type"] != "olmo_hybrid":
        raise ValueError("not an olmo_hybrid configuration")
    if config["hidden_act"] != "silu":
        raise ValueError("the program's feed-forward is a SwiGLU only")
    if config["attention_bias"]:
        raise ValueError("the program's projections have no bias")
    if config["tie_word_embeddings"]:
        raise ValueError("the program's head is its own matrix")
    if config["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("the program's attention rotates nothing here")
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("the program's mixer has one state a head: as "
                         "many key heads as value heads")
    if not config["linear_allow_neg_eigval"]:
        raise ValueError("the program's write gate carries the factor 2")
    if config["hidden_size"] % config["num_attention_heads"]:
        raise ValueError("the attention heads do not divide the hidden size")
    if len(config["layer_types"]) != config["num_hidden_layers"] \
            or set(config["layer_types"]) - set(KINDS):
        raise ValueError(f"layer_types has one entry a layer, each one of "
                         f"{KINDS}")
    prog = config["program"]
    if config["train"]["seq_len"] > config["max_position_embeddings"] \
            or config["train"]["seq_len"] % prog["delta_chunk_size"]:
        raise ValueError("the sequence is longer than the positions, or "
                         "no multiple of the delta rule's chunk")
    return OlmoHybrid(OlmoHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        max_seq_len=config["max_position_embeddings"],
        rms_eps=config["rms_norm_eps"],
        intermediate_size=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        linear_heads=config["linear_num_value_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        chunk_size=prog["delta_chunk_size"],
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def reference_loss(config: dict, **control):
    """`loss(params, batch)` of the plain reference for this file;
    `control` is `dtype=` or `wrong=` of `reference_olmo_hybrid.forward`
    (the controls one precision below and one term wrong)."""
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("the reference's attention has no grouped heads")
    return functools.partial(
        reference_olmo_hybrid.loss,
        layer_types=tuple(config["layer_types"]),
        n_head=config["num_attention_heads"],
        linear_heads=config["linear_num_value_heads"],
        key_dim=config["linear_key_head_dim"],
        value_dim=config["linear_value_head_dim"],
        eps=config["rms_norm_eps"], **control)


def _counts(config: dict) -> tuple:
    kinds = config["layer_types"]
    return kinds.count("linear_attention"), kinds.count("full_attention")


def _recurrence_flops_per_token(config: dict) -> int:
    """The RECURRENCE's operations of one gated delta-rule layer for one
    token, forward, as its equation is written: per HELD head the decay
    of S (dk*dv products), S^T k (2*dk*dv), the outer product k u^T
    (dk*dv), its sum into S (dk*dv) and o = S^T q (2*dk*dv): 7*dk*dv a
    head (the dv-long difference and its gate are left out).  What a
    chunked form spends beyond that (the (C x C) products, the solve, the
    (dk x dk) transitions) is that form's own cost, not counted."""
    return 7 * config["linear_key_head_dim"] \
        * config["linear_value_head_dim"] * config["linear_num_value_heads"]


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through — every block's SwiGLU, a linear mixer's
    seven projections at the HELD heads, an attention layer's four, the
    untied head (the embedding is a lookup) — plus causal attention's
    6*T*hidden an attention layer and three times the recurrence's
    forward operations a linear layer.  The convolution, norms and gates
    are left out; recomputation is not counted."""
    h = config["hidden_size"]
    n_lin, n_attn = _counts(config)
    heads = config["linear_num_value_heads"]
    qk = heads * config["linear_key_head_dim"]
    v = heads * config["linear_value_head_dim"]
    linear = h * (2 * qk + 2 * v + 2 * heads) + v * h
    attn = 4 * h * h
    mlp = 3 * h * config["intermediate_size"]
    params = n_lin * linear + n_attn * attn + (n_lin + n_attn) * mlp \
        + h * config["vocab_size"]
    return 6.0 * params + 6.0 * n_attn * config["train"]["seq_len"] * h \
        + 3.0 * n_lin * _recurrence_flops_per_token(config)


def attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """Causal attention FLOPs and bytes of one optimizer step over the
    whole batch, all `full_attention` layers (as many key/value heads as
    query heads: `flops.causal_attention_cost` as it stands)."""
    heads = config["num_attention_heads"]
    one = flops.causal_attention_cost(
        global_batch, heads, config["train"]["seq_len"],
        config["hidden_size"] // heads, bytes_per_el=2)
    return {k: v * _counts(config)[1] for k, v in one.items()}


def delta_cost_per_step(config: dict, global_batch: int,
                        bytes_per_el: int = 2) -> dict:
    """FLOPs and least HBM bytes of the gated delta rule of one optimizer
    step, forward + backward, all `linear_attention` layers, the HELD
    heads.

    FLOPs: the RECURRENCE's (`_recurrence_flops_per_token`), twice that
    backward — not the chunked form's, so that no choice of chunk size
    moves the count.  Bytes: each of q and k (H*dk), v and o (H*dv), the
    decay and the write gate (H each) read or written once forward, and
    once more backward (their gradients), at `bytes_per_el`; a state
    that never leaves the chip's fast memory.  Both err low: the share of
    the roofline this gives cannot pass 100% by a later change of form."""
    tokens = global_batch * config["train"]["seq_len"]
    heads = config["linear_num_value_heads"]
    one_way = tokens * heads * (2 * config["linear_key_head_dim"]
                                + 2 * config["linear_value_head_dim"]
                                + 2) * bytes_per_el
    fwd = tokens * _recurrence_flops_per_token(config)
    one = {"flops_fwd": fwd, "flops_bwd": 2 * fwd, "flops": 3 * fwd,
           "bytes_fwd": one_way, "bytes_bwd": one_way, "bytes": 2 * one_way}
    return {k: v * _counts(config)[0] for k, v in one.items()}
