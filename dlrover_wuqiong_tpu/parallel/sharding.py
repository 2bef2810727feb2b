"""Sharding-spec library: parameter/activation PartitionSpecs for transformers.

Parity: reference atorch TP modules — `RowParallelLinear`
(`modules/distributed_modules/layers.py:239`), `ColumnParallelLinear` (:392),
`VocabParallelEmbedding` (:549), the collective autograd functions
(`mappings.py:302-430`) and the operator-replacement registry
(`modules_registry.py`).

TPU redesign: Megatron-style row/column parallelism is *not* hand-written
collectives — it is a PartitionSpec per parameter plus GSPMD propagation.
A column-parallel linear is kernel P(None, "tp"); row-parallel is
P("tp", None) (XLA inserts the reduce-scatter/all-reduce the mappings.py
autograd functions implement by hand).  FSDP (ZeRO-3) adds sharding of every
param along "fsdp".  This module maps parameter *path patterns* → specs, the
single source of truth used by trainers and the checkpoint engine.

Activations.  Parameter specs alone leave the partitioner free to choose
where activations live, and it moves whichever operand is cheaper by its own
count: under fsdp it kept the kernels sharded on their contracting
dimension, re-laid the residual stream from batch-sharded to feature-sharded
and all-reduced full-batch activations in every dense layer (PERF.md,
PR 25).  So every sharded plan GUARANTEES one layout for the residual
stream, `activation_spec`: batch over ("dp", "fsdp"), sequence over "sp"
when sp > 1, features whole (replicated over "tp", as Megatron keeps it).
The model states it — `pin_activation(x, cfg.mesh)` at the entry of each
block (models/gpt.py `Block`, models/llama.py `LlamaBlock`), inside the
block so that remat's recomputed forward and the backward carry it — and a
dense layer is left one cheap choice: all-gather the kernel, multiply the
chip's own tokens, sum the weight gradient across chips.  On one device the
model has no mesh and the helper returns its argument: no op is traced.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.log import get_logger

logger = get_logger("sharding")


Rule = Tuple[str, P]  # (path regex, spec)


# Default rules for transformer LMs (flax param-tree paths).  Order matters:
# first match wins.  Conventions: embedding tables (vocab, embed);
# attention/MLP kernels (in_features, out_features).
TRANSFORMER_RULES: List[Rule] = [
    # embeddings: vocab-parallel over tp (parity VocabParallelEmbedding :549)
    (r".*(wte|embed_tokens|token_embed|embedding)/embedding$",
     P("tp", "fsdp")),
    (r".*(wpe|pos_embed)/embedding$", P(None, "fsdp")),
    # attention qkv: column-parallel (heads split over tp)
    (r".*(attn|attention).*(q_proj|k_proj|v_proj|qkv|c_attn|query|key|value)"
     r"/kernel$", P("fsdp", "tp")),
    # latent attention (models/latent_attention.py): the down-projection
    # to the latent and the ONE rotated key part is small and feeds every
    # head, so its columns stay whole; the up-projection from the normed
    # latent is column-parallel (heads split over tp) as q_proj is; the
    # latent's norm is a norm
    (r".*(attn|attention).*kv_a_proj/kernel$", P("fsdp", None)),
    (r".*(attn|attention).*kv_b_proj/kernel$", P("fsdp", "tp")),
    (r".*(attn|attention).*kv_a_norm/scale$", P()),
    # a latent for q goes as the one for keys and values: whole columns
    # down, heads over tp up, a norm between
    (r".*(attn|attention).*q_a_proj/kernel$", P("fsdp", None)),
    (r".*(attn|attention).*q_b_proj/kernel$", P("fsdp", "tp")),
    (r".*(attn|attention).*q_a_norm/scale$", P()),
    # a hyper-connection (models/hyper_connection.py): Phi's rows are the
    # lanes' hidden features, its 24 columns stay whole; gains and biases
    # are a few numbers
    (r".*_hc/phi$", P(None, "fsdp", None)),
    (r".*_hc/(alpha|b_pre|b_post|b_res)$", P()),
    # a multi-token-prediction module's joining product: column-parallel
    (r".*mtp_\d+/eh_proj/kernel$", P("fsdp", "tp")),
    # a per-head gate on the attention's output (LlamaConfig.attn_gate):
    # one column a head, split over tp as q_proj's heads are
    (r".*(attn|attention).*g_proj/kernel$", P("fsdp", "tp")),
    # attention out: row-parallel (parity RowParallelLinear :239)
    (r".*(attn|attention).*(o_proj|out_proj|c_proj|dense|out)/kernel$",
     P("tp", "fsdp")),
    # MLP up/gate: column-parallel
    (r".*(mlp|ffn|feed_forward).*(up_proj|gate_proj|c_fc|fc1|w1|w3)/kernel$",
     P("fsdp", "tp")),
    # MLP down: row-parallel
    (r".*(mlp|ffn|feed_forward).*(down_proj|c_proj|fc2|w2)/kernel$",
     P("tp", "fsdp")),
    # a state-space mixer's two projections (models/mamba2.py): column-
    # then row-parallel, as an MLP's; its filter, its per-head leaves and
    # an expert router's selection bias are small and replicated
    (r".*mamba.*in_proj/kernel$", P("fsdp", "tp")),
    (r".*mamba.*out_proj/kernel$", P("tp", "fsdp")),
    (r".*mamba.*/(conv_kernel|conv_bias|A_log|D|dt_bias)$", P()),
    # a gated delta-rule mixer (models/gated_delta.py, models/kda.py): q,
    # k, v and o go by the attention rules above, and so does a KDA
    # mixer's head-wise output gate (g_proj, a column a head: the
    # attention gate's rule above binds first); the element-wise output
    # gate's projection and the channel decay's (f_proj) are column-
    # parallel as v's is, the per-head gates' projections are a few
    # columns and stay whole, the filter and the per-head or per-channel
    # leaves are small and replicated
    (r".*linear_attention.*(g_proj|f_proj)/kernel$", P("fsdp", "tp")),
    (r".*linear_attention.*(a_proj|b_proj)/kernel$", P("fsdp", None)),
    (r".*linear_attention.*/(conv_kernel|A_log|dt_bias)$", P()),
    # a gated short-convolution mixer (models/lfm2.py): its two
    # projections column- then row-parallel, as a state-space mixer's; its
    # filter is a few numbers a channel and replicated
    (r".*short_conv.*in_proj/kernel$", P("fsdp", "tp")),
    (r".*short_conv.*out_proj/kernel$", P("tp", "fsdp")),
    (r".*short_conv.*/conv_kernel$", P()),
    # a sparse attention's indexer (models/sparse_indexer.py): its query
    # heads column-parallel as q's are; the ONE key's projection and the
    # weight-a-head's are a few columns and stay whole; its LayerNorm
    # goes by the norms' rule below
    (r".*indexer.*wq_idx/kernel$", P("fsdp", "tp")),
    (r".*indexer.*(wk_idx|w_proj)/kernel$", P("fsdp", None)),
    (r".*selection_bias$", P()),
    # lm head: vocab-parallel
    (r".*(lm_head|output_proj)/kernel$", P("fsdp", "tp")),
    # biases follow their kernel's output dim
    (r".*(q_proj|k_proj|v_proj|qkv|c_attn|up_proj|gate_proj|c_fc|fc1|w1|w3)"
     r"/bias$", P("tp")),
    # norms, scalars: replicated (but fsdp-shard 1D when large? keep simple)
    (r".*(ln|norm|layernorm|rmsnorm).*", P()),
    (r".*/bias$", P()),
    (r".*scale$", P()),
]

MOE_RULES: List[Rule] = [
    # expert weights: (num_experts, in, out) — experts over ep
    (r".*experts.*(w_in|w_gate|w1|w3|up|gate).*", P("ep", "fsdp", "tp")),
    (r".*experts.*(w_down|w2|down).*", P("ep", "tp", "fsdp")),
    (r".*(router|gate)/kernel$", P("fsdp", None)),
]


def path_of(key_path) -> str:
    import jax

    parts = []
    for p in key_path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def spec_for_path(path: str, rules: Sequence[Rule],
                  ndim: Optional[int] = None) -> P:
    for pattern, spec in rules:
        if re.match(pattern, path, re.IGNORECASE):
            if ndim is not None:
                spec = _fit_spec(spec, ndim)
            return spec
    return P()  # default: replicated (fsdp handled by fsdp_wrap below)


def _fit_spec(spec: P, ndim: int) -> P:
    """Trim/pad a spec to the array's rank."""
    parts = list(spec)
    if len(parts) > ndim:
        parts = [p for p in parts if p is not None][:ndim]
        parts += [None] * (ndim - len(parts))
    elif len(parts) < ndim:
        parts += [None] * (ndim - len(parts))
    return P(*parts)


def _add_fsdp(spec: P, shape: Tuple[int, ...], mesh: Mesh,
              min_size: int = 2 ** 16) -> P:
    """ZeRO-3: also shard large replicated-dim params along "fsdp".

    Picks the largest dim not already sharded and divisible by the fsdp size.
    Parity: reference FSDPOptimization (zero_optimization.py:240) auto-wrap —
    in GSPMD it's just one more mesh axis in the spec.
    """
    fsdp_size = mesh.shape.get("fsdp", 1)
    if fsdp_size <= 1:
        return spec
    if "fsdp" in [a for part in spec if part for a in
                  (part if isinstance(part, tuple) else (part,))]:
        return spec
    if math.prod(shape) < min_size:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    # choose largest unsharded, divisible dim
    best, best_size = -1, 0
    for i, (dim, cur) in enumerate(zip(shape, parts)):
        if cur is None and dim % fsdp_size == 0 and dim > best_size:
            best, best_size = i, dim
    if best < 0:
        return spec
    parts[best] = "fsdp"
    return P(*parts)


@dataclass
class ShardingPlanner:
    """Maps a param pytree to NamedShardings over a mesh."""

    mesh: Mesh
    rules: List[Rule] = field(default_factory=lambda:
                              list(TRANSFORMER_RULES))
    fsdp_min_size: int = 2 ** 16

    def with_moe(self) -> "ShardingPlanner":
        self.rules = list(MOE_RULES) + self.rules
        return self

    def param_specs(self, params: Any) -> Any:
        """Pytree of PartitionSpec matching `params` structure."""
        import jax

        def _spec(key_path, leaf):
            path = path_of(key_path)
            spec = spec_for_path(path, self.rules,
                                 ndim=getattr(leaf, "ndim", None))
            shape = getattr(leaf, "shape", ())
            spec = _add_fsdp(spec, tuple(shape), self.mesh,
                             self.fsdp_min_size)
            return spec

        return jax.tree_util.tree_map_with_path(_spec, params)

    def param_shardings(self, params: Any) -> Any:
        import jax

        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.param_specs(params),
                            is_leaf=lambda x: isinstance(x, P))

    def shard_params(self, params: Any) -> Any:
        """Place a host/replicated param pytree onto the mesh."""
        import jax

        return jax.device_put(params, self.param_shardings(params))

    # ------------------------------------------------------------ activations

    def batch_spec(self, ndim: int = 2, seq_axis: Optional[int] = None,
                   batch_axis: int = 0) -> P:
        """Batch activations: batch dim over (dp, fsdp), optional seq over sp.

        `batch_axis` > 0 supports a leading grad-accum microbatch axis
        (replicated — each accumulation step runs on the whole mesh).
        """
        parts: List[Any] = [None] * ndim
        parts[batch_axis] = ("dp", "fsdp")
        sp = self.mesh.shape.get("sp", 1)
        if seq_axis is not None and sp > 1:
            parts[seq_axis] = "sp"
        return P(*parts)

    def batch_sharding(self, ndim: int = 2, seq_axis: Optional[int] = None,
                       batch_axis: int = 0) -> NamedSharding:
        return NamedSharding(self.mesh,
                             self.batch_spec(ndim, seq_axis, batch_axis))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def constrain(x, mesh: Mesh, spec: P):
    """In-jit sharding hint (the GSPMD equivalent of mappings.py collectives)."""
    import jax

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def activation_spec(mesh: Optional[Mesh],
                    shape: Sequence[int] = (0, 0, 0)) -> Optional[P]:
    """Where a (batch, sequence, features) activation lives on `mesh`:
    `ShardingPlanner.batch_spec` — batch over ("dp", "fsdp"), sequence
    over "sp" when sp > 1, features whole.  A dimension its axes do not
    divide (a batch-of-one init) is left to the partitioner.  So is the
    batch inside an enclosing shard_map: a pipeline stage's microbatch
    or a DiLoCo group's share is that map's to lay out.  Without a
    `shape` every dimension counts as divisible: the layout by itself.
    None when there is nothing to state: no mesh, or a single device.
    """
    if mesh is None or mesh.size == 1:
        return None
    from jax.sharding import get_abstract_mesh

    parts = list(ShardingPlanner(mesh).batch_spec(len(shape), seq_axis=1))
    for i, (axes, dim) in enumerate(zip(parts, shape)):
        axes = axes if isinstance(axes, tuple) else (axes,)
        if dim % math.prod(mesh.shape[a] for a in axes if a):
            parts[i] = P.UNCONSTRAINED
    if get_abstract_mesh().manual_axes:
        parts[0] = P.UNCONSTRAINED
    return P(*parts)


def pin_activation(x, mesh: Optional[Mesh]):
    """State the layout of a residual-stream activation (`activation_spec`)
    so that a dense layer leaves the partitioner one cheap choice: gather
    the kernel, multiply the chip's own tokens, sum the weight gradient.
    Without a mesh of several devices this returns `x` itself — no op
    enters the jaxpr."""
    spec = activation_spec(mesh, x.shape)
    if spec is None:
        return x
    from .mesh import context_mesh

    return constrain(x, context_mesh(mesh), spec)
