"""Model class `phi4flash`: how a Phi-4-mini-flash-reasoning-shaped
configuration file (the source's own HF keys) becomes the program's
module — `models/phi4flash.py`'s decoder-hybrid-decoder stack: Mamba-1
mixers (`models/mamba1.py`, `ops/selective_scan.py`), differential
attention under a window or none (ONE two-width call of
`ops/flash_attention.py`'s kernels a layer), one full layer whose keys
and values the cross-decoder reads, gated memory units on the last
scan's output, a SwiGLU behind every mixer, LayerNorms with bias, a tied
head — its plain reference (`reference_phi4flash.py`), and its operation
counts.

The file's `num_hidden_layers` is how many layers are HELD;
`share.layer_ids` names them by their PUBLISHED indices, which say each
layer's kind and its lam0.
"""

from __future__ import annotations

import functools

from benchmark import reference_phi4flash
from benchmark.models import gpt

seeded_state = gpt.seeded_state  # the draw every model class makes


def _layer_ids(config: dict) -> tuple:
    return tuple(config["share"]["layer_ids"])


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.phi4flash import (
        Phi4Flash,
        Phi4FlashConfig,
    )

    if config["model_type"] != "phi4flash":
        raise ValueError("not a phi4flash configuration")
    if config["hidden_act"] != "silu":
        raise ValueError("the program's feed-forward is a SwiGLU")
    if config["mlp_bias"] or config["lm_head_bias"]:
        raise ValueError("the program's feed-forward and head have no bias")
    if not config["tie_word_embeddings"]:
        raise ValueError("the program's head is the embedding")
    if config["embd_pdrop"] or config["resid_pdrop"]:
        raise ValueError("dropout is not run by the benchmark")
    if config["num_key_value_heads"] % 2 \
            or config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("differential attention pairs the heads: an even "
                         "number of kv heads that divides the query heads")
    ids, share = _layer_ids(config), config["share"]
    if len(ids) != config["num_hidden_layers"] or list(ids) != sorted(
            set(ids)) or ids[-1] >= share["num_hidden_layers_published"]:
        raise ValueError("share.layer_ids names num_hidden_layers published "
                         "layers, in order")
    if config["train"]["seq_len"] > config["max_position_embeddings"]:
        raise ValueError("the sequence is longer than the positions")
    prog, mamba = config["program"], config["assumed"]["mamba"]
    cfg = Phi4FlashConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        sliding_window=config["sliding_window"],
        mb_per_layer=config["mb_per_layer"],
        num_layers_published=share["num_hidden_layers_published"],
        layer_ids=ids, max_seq_len=config["max_position_embeddings"],
        norm_eps=config["layer_norm_eps"], mamba_expand=mamba["expand"],
        mamba_state_size=mamba["d_state"], mamba_conv_kernel=mamba["d_conv"],
        mamba_dt_rank=mamba["dt_rank"],
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"])
    kinds = [cfg.kind(i) for i in ids]
    for reader, source in (("gmu", cfg.memory_layer),
                           ("cross", cfg.kv_layer)):
        if reader in kinds and source not in ids[:kinds.index(reader)]:
            raise ValueError(f"a {reader} layer is held without layer "
                             f"{source}, which hands on what it reads")
    return Phi4Flash(cfg)


def reference_loss(config: dict, **over):
    """`loss(params, batch)` of the plain reference for this file;
    `over` sets a control (`dtype=`, `wrong=`)."""
    return functools.partial(
        reference_phi4flash.loss, **{**dict(
            layer_ids=_layer_ids(config),
            n_published=config["share"]["num_hidden_layers_published"],
            mb_per_layer=config["mb_per_layer"],
            n_head=config["num_attention_heads"],
            n_kv_head=config["num_key_value_heads"],
            window=config["sliding_window"],
            state=config["assumed"]["mamba"]["d_state"],
            eps=config["layer_norm_eps"]), **over})


# ------------------------------------------------------------- the counts

def _kinds(config: dict) -> dict:
    """{kind: layers held}, by `reference_phi4flash.kind_of`."""
    kinds = [reference_phi4flash.kind_of(
        i, config["share"]["num_hidden_layers_published"],
        config["mb_per_layer"]) for i in _layer_ids(config)]
    return {kind: kinds.count(kind) for kind in
            ("mamba", "gmu", "window", "full", "cross")}


def _d_inner(config: dict) -> int:
    return config["assumed"]["mamba"]["expand"] * config["hidden_size"]


def kept_pairs(seq: int, window=None) -> int:
    """(query, key) pairs one head keeps over a sequence: the causal
    triangle, or under a window the band window x seq - window x
    (window - 1) / 2."""
    w = seq if window is None else min(window, seq)
    return w * seq - w * (w - 1) // 2


def _scan_ops_per_token(config: dict) -> int:
    """The RECURRENCE's operations of one Mamba-1 layer for one token,
    forward, as its equation is written, a (channel, state) pair: dt A,
    its exp, the decay of h, (dt x) B, their sum, h C and y's sum — 7."""
    return 7 * _d_inner(config) * config["assumed"]["mamba"]["d_state"]


def dense_params_per_token(config: dict) -> dict:
    """Matmul parameters one token passes through, by part (the
    embedding is a lookup; the tied head's product is counted once)."""
    h, n = config["hidden_size"], _kinds(config)
    mamba, di = config["assumed"]["mamba"], _d_inner(config)
    q = h  # heads x head size is the hidden size
    kv = q * config["num_key_value_heads"] // config["num_attention_heads"]
    return {
        "mamba": n["mamba"] * (h * 2 * di + di * (
            mamba["dt_rank"] + 2 * mamba["d_state"])
            + mamba["dt_rank"] * di + di * h),
        "gmu": n["gmu"] * 2 * h * di,
        "attention": (n["window"] + n["full"]) * (h * (q + 2 * kv) + q * h)
        + n["cross"] * 2 * h * q,
        "mlp": config["num_hidden_layers"] * 3 * h
        * config["intermediate_size"],
        "head": h * config["vocab_size"]}


def _pair_flops(config: dict) -> int:
    """FORWARD FLOPs a kept pair of one head of the kernels' call: QK^T
    over d = 64 and PV over [v1 | v2] = 128."""
    d = config["hidden_size"] // config["num_attention_heads"]
    return 2 * d + 2 * 2 * d


def _pairs_per_sequence(config: dict) -> int:
    """Kept pairs of every attention layer and head of one sequence."""
    n, seq = _kinds(config), config["train"]["seq_len"]
    return config["num_attention_heads"] * (
        n["window"] * kept_pairs(seq, config["sliding_window"])
        + (n["full"] + n["cross"]) * kept_pairs(seq))


def train_flops_per_token(config: dict) -> float:
    """Forward + backward FLOPs one token requires: 6 per matmul
    parameter it passes through, three times the forward FLOPs of the
    attention's two products over the KEPT pairs (the band under the
    window, q and k at 64 and v at 128 a head), and three times the
    recurrence's forward operations a Mamba-1 layer.  The convolution,
    norms, gates and the differential combination are left out;
    recomputation is not counted."""
    return 6.0 * sum(dense_params_per_token(config).values()) \
        + 3.0 * _pair_flops(config) * _pairs_per_sequence(config) \
        / config["train"]["seq_len"] \
        + 3.0 * _kinds(config)["mamba"] * _scan_ops_per_token(config)


def _attention_cost(config: dict, global_batch: int, pairs: int,
                    layers: int, bytes_per_el: int) -> dict:
    """`flops.causal_attention_cost`'s counting at two widths over
    `pairs` kept pairs (all heads, one sequence) and `layers` layers'
    tensors: forward QK^T and PV, backward dV, dP (over 2d) and dQ, dK
    (over d); of the bytes q and dq (d) and o and dO (2d) once a query
    head, k, v and their gradients once as the model holds them (KV
    heads of d each)."""
    d = config["hidden_size"] // config["num_attention_heads"]
    seq = config["train"]["seq_len"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    fwd = global_batch * pairs * _pair_flops(config)
    bwd = 2 * fwd  # dV and dP over 2d, dQ and dK over d
    q_t = layers * global_batch * heads * seq * d * bytes_per_el
    o_t = 2 * q_t
    kv_t = layers * global_batch * kv * seq * d * bytes_per_el
    return {"flops_fwd": fwd, "flops_bwd": bwd, "flops": fwd + bwd,
            "bytes_fwd": q_t + o_t + 2 * kv_t,
            "bytes_bwd": 2 * q_t + 2 * o_t + 4 * kv_t,
            "bytes": 3 * q_t + 3 * o_t + 6 * kv_t}


def attention_cost_per_step(config: dict, global_batch: int,
                            bytes_per_el: int = 2) -> dict:
    """The attention's FLOPs and least HBM bytes of one optimizer step
    over the whole batch, all three kinds of attention layer, forward +
    backward, over the KEPT pairs: the band under the window, the
    triangle elsewhere."""
    n = _kinds(config)
    return _attention_cost(config, global_batch, _pairs_per_sequence(config),
                           n["window"] + n["full"] + n["cross"],
                           bytes_per_el)


def window_attention_cost_per_step(config: dict, global_batch: int,
                                   bytes_per_el: int = 2) -> dict:
    """The same for the WINDOWED layers alone (`dwt_fa_win_*`)."""
    n = _kinds(config)["window"]
    pairs = config["num_attention_heads"] * n * kept_pairs(
        config["train"]["seq_len"], config["sliding_window"])
    return _attention_cost(config, global_batch, pairs, n, bytes_per_el)


def sscan_cost_per_step(config: dict, global_batch: int,
                        bytes_per_el: int = 2) -> dict:
    """Operations and least HBM bytes of the selective scan of one
    optimizer step, forward + backward, all Mamba-1 layers.

    Operations: the RECURRENCE's (`_scan_ops_per_token`), twice that
    backward.  Bytes: each of x, dt and y (d_inner) and B and C
    (d_state) read or written once forward, and once more backward
    (their gradients), at `bytes_per_el`; a state that never leaves the
    chip's fast memory.  Both err low — and the operations are the
    vector units' and the exponent unit's, for which `peaks.json` has no
    peak: `flops.roofline` holds them to the matrix unit's, so the share
    this gives errs lower still, never high."""
    tokens = global_batch * config["train"]["seq_len"]
    one_way = tokens * (3 * _d_inner(config)
                        + 2 * config["assumed"]["mamba"]["d_state"]) \
        * bytes_per_el
    fwd = tokens * _scan_ops_per_token(config)
    one = {"flops_fwd": fwd, "flops_bwd": 2 * fwd, "flops": 3 * fwd,
           "bytes_fwd": one_way, "bytes_bwd": one_way, "bytes": 2 * one_way}
    return {k: v * _kinds(config)["mamba"] for k, v in one.items()}
