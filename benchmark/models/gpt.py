"""Model class `gpt`: how a GPT-2-shaped configuration file becomes the
program's module, its plain reference, and its operation counts.

A later PR that brings another architecture (OLMoE, ...) adds a file
beside this one with the same five functions and names it in its
configuration's `model_class`.
"""

from __future__ import annotations

import functools

from benchmark import flops, reference

_PROGRAM_LN_EPS = 1e-6  # flax.linen.LayerNorm's default, which GPT uses


def build(config: dict):
    """The program's module for this configuration file."""
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

    if config["activation_function"] != "gelu_new":
        raise ValueError("the program's MLP is GELU(tanh) only")
    if config["layer_norm_epsilon"] != _PROGRAM_LN_EPS:
        raise ValueError(
            f"the program's LayerNorm epsilon is {_PROGRAM_LN_EPS}; the "
            f"configuration file must say what is run")
    if any(config[k] for k in ("attn_pdrop", "embd_pdrop", "resid_pdrop")):
        raise ValueError("dropout is not run by the benchmark")
    if not config["tie_word_embeddings"] or config["n_inner"] is not None:
        raise ValueError("the program's GPT ties its head and uses 4x MLPs")
    prog = config["program"]
    return GPT(GPTConfig(
        vocab_size=config["vocab_size"], n_layer=config["n_layer"],
        n_head=config["n_head"], n_embd=config["n_embd"],
        block_size=config["n_positions"], dropout=0.0,
        dtype=getattr(jnp, prog["dtype"]), remat=prog["remat"],
        remat_policy=prog["remat_policy"],
        use_flash_attention=prog["use_flash_attention"]))


def seeded_state(trainer, seed: int):
    """The train state drawn on the device from `seed`, in ONE jitted
    call, sharded as the Trainer's own init shards it.  The Trainer's
    init always draws from PRNGKey(0); the key is an ARGUMENT here, so
    every seed runs the same cached program."""
    import jax

    from dlrover_wuqiong_tpu.trainer.train_step import TrainState

    model, optimizer = trainer.res.model, trainer.optimizer
    for leaf in jax.tree.leaves(trainer.state):
        if not leaf.is_deleted():
            leaf.delete()  # free the old state before the new one lands

    create = getattr(trainer, "_bench_seeded_init", None)
    if create is None:  # traced once per process, however often it is drawn
        create = trainer._bench_seeded_init = jax.jit(
            lambda key: TrainState.create(model.init_params(key), optimizer),
            out_shardings=trainer.res.state_shardings)
    state = create(jax.random.PRNGKey(seed))
    trainer.res.state = state
    return state


def reference_loss(config: dict):
    """`loss(params, batch)` of the plain reference for this file."""
    return functools.partial(
        reference.loss, n_layer=config["n_layer"], n_head=config["n_head"],
        eps=config["layer_norm_epsilon"])


def train_flops_per_token(config: dict) -> float:
    return flops.gpt_train_flops_per_token(
        config["vocab_size"], config["n_positions"], config["n_layer"],
        config["n_embd"], config["train"]["seq_len"])


def attention_cost_per_step(config: dict, global_batch: int) -> dict:
    """Causal attention FLOPs and bytes of one optimizer step over the
    whole batch, all layers (divide by chips for one chip's share)."""
    one = flops.causal_attention_cost(
        global_batch, config["n_head"], config["train"]["seq_len"],
        config["n_embd"] // config["n_head"], bytes_per_el=2)
    return {k: v * config["n_layer"] for k, v in one.items()}
