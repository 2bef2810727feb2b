"""`nemotron_h`: a hybrid stack whose layers are drawn from a pattern
string — `M` a Mamba-2 mixer (models/mamba2.py), `E` an expert layer
(models/moe.py: sigmoid router with a selection bias, relu^2 experts, a
shared expert, and where told so a chip's share of the experts), `*`
grouped-query attention without rotary embedding (models/llama.py's
`LlamaAttention`; the Mamba layers carry the positions).

    x = embed[ids]
    for kind in pattern:  x = x + mixer_kind(RMSNorm(x))
    logits = RMSNorm(x) @ head                      (untied)

ONE mixer a block behind one RMSNorm, so the layers of a stack cost
unequal amounts.  Parameter names are `layers_<i>/norm` and
`layers_<i>/{mamba|feed_forward|attention}`, matched by
`parallel/sharding.py`; the embedding's and the head's are
`models/llama.py`'s, the stack `models/stack.py`'s.

Parity: none — the reference trains Llama/GLM-class stacks only
(models/llama.py); this stack exists for the hybrid's benchmark cell.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from ..parallel.sharding import pin_activation
from . import stack
from .llama import LlamaAttention, LlamaConfig, RMSNorm
from .mamba2 import Mamba2Config, Mamba2Mixer
from .moe import MoEConfig, MoEMLP

KINDS = {"M": "mamba", "E": "feed_forward", "*": "attention"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    max_seq_len: int = 262144
    rms_eps: float = 1e-5
    # `*`: grouped-query attention, heads x head_dim need not be hidden
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # `M`: Mamba-2
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    # `E`: experts
    num_experts: int = 128
    top_k: int = 6
    expert_width: int = 1856
    shared_width: int = 3712
    routed_scaling: float = 2.5
    norm_topk_prob: bool = True
    experts_held: int = 0  # 0 = all; else a chip's share, from first_expert
    first_expert: int = 0
    # the selection bias's out-of-band rule (MoEConfig.bias_update_rate);
    # 0 = not run
    bias_update_rate: float = 0.0
    # the program
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"
    use_flash_attention: bool = True
    mesh: Any = None

    @classmethod
    def nano(cls, **over):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, pattern="MEM*E", max_seq_len=64,
            num_heads=4, num_kv_heads=2, head_dim=32, mamba_heads=8,
            mamba_head_dim=16, n_groups=2, state_size=16, chunk_size=16,
            num_experts=8, top_k=2, expert_width=32, shared_width=48),
            **over})

    def attention_config(self) -> LlamaConfig:
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            num_layers=self.pattern.count("*"), num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, attn_head_dim=self.head_dim,
            max_seq_len=self.max_seq_len, rms_eps=self.rms_eps,
            dtype=self.dtype, use_flash_attention=self.use_flash_attention,
            mesh=self.mesh, rope=False)

    def mamba_config(self) -> Mamba2Config:
        return Mamba2Config(
            hidden_size=self.hidden_size, num_heads=self.mamba_heads,
            head_dim=self.mamba_head_dim, n_groups=self.n_groups,
            state_size=self.state_size, conv_kernel=self.conv_kernel,
            chunk_size=self.chunk_size, eps=self.rms_eps, dtype=self.dtype,
            mesh=self.mesh, dt_min=self.dt_min, dt_max=self.dt_max,
            dt_floor=self.dt_floor)

    def moe_config(self) -> MoEConfig:
        return MoEConfig(
            num_experts=self.num_experts, top_k=self.top_k, impl="grouped",
            dtype=self.dtype, norm_topk_prob=self.norm_topk_prob,
            aux_loss="none", aux_loss_weight=0.0, score_func="sigmoid",
            selection_bias=True, routed_scaling=self.routed_scaling,
            expert_act="relu2", shared_width=self.shared_width,
            experts_held=self.experts_held, first_expert=self.first_expert,
            bias_update_rate=self.bias_update_rate, mesh=self.mesh)

    def num_params(self) -> int:
        h = self.hidden_size
        ffn = dataclasses.replace(
            self.attention_config(), moe=self.moe_config(),
            intermediate_size=self.expert_width).ffn_params()
        mixer = {"M": self.mamba_config().num_params(), "E": ffn,
                 "*": self.attention_config().attention_params()}
        return (2 * self.vocab_size * h + h
                + sum(mixer[kind] + h for kind in self.pattern))


class NemotronHBlock(nn.Module):
    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        x = pin_activation(x, cfg.mesh)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x)
        if self.kind == "M":
            out = Mamba2Mixer(cfg.mamba_config(), name=KINDS["M"])(h)
        elif self.kind == "E":
            out = MoEMLP(cfg.hidden_size, cfg.expert_width,
                         cfg.moe_config(), name=KINDS["E"])(h)
        else:
            out = LlamaAttention(cfg.attention_config(), name=KINDS["*"])(
                h, None, None)
        # the save/offload anchor of the *_names remat policies
        name = "attn_out" if self.kind == "*" else "mlp_out"
        return x + checkpoint_name(out, name)


class NemotronH(nn.Module):
    config: NemotronHConfig

    # leaves the optimizer leaves alone, neither step nor weight decay
    # (whole paths, as train_step.leave_untouched matches them): the
    # selection bias has no gradient, and the rule that updates it out
    # of band is not part of the config
    untrained_params = (r"layers_\d+/feed_forward/selection_bias",)

    @nn.compact
    def __call__(self, idx):
        cfg = self.config
        if set(cfg.pattern) - set(KINDS):
            raise ValueError(f"pattern {cfg.pattern!r}: a layer is one of "
                             f"{sorted(KINDS)}")
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(idx)
        x = stack.layers(NemotronHBlock, cfg,
                         [(kind,) for kind in cfg.pattern], x)
        return stack.untied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
            cfg.vocab_size, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 0):
        return stack.init_params(self, rng, batch,
                                 seq or self.config.chunk_size)
