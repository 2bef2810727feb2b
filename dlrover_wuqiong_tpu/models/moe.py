"""Mixture-of-Experts layer with expert parallelism, TPU-first.

Parity: reference `atorch/atorch/modules/moe/` — `MOELayer`/`Experts`
(moe_layer.py:29-116, `_AllToAll` :87), `topk_gating.py`, `switch_gating.py`,
`grouped_gemm_moe.py`.

TPU redesign: experts live as a stacked (E, d_in, d_out) parameter sharded
P("ep", ...) on the mesh.  Routing is dense capacity-based dispatch — a
one-hot combine tensor contracted with einsum, the canonical XLA MoE shape
(Switch/GShard style): no ragged host loops, everything static for the MXU.
GSPMD inserts the all-to-alls from the shardings; an explicit shard_map
dispatch is unnecessary on TPU, which is exactly the "GSPMD over hand-written
collectives" design stance (SURVEY.md §7).

Load-balancing aux loss: Switch Transformer's by default (top-1 fraction *
mean router prob per expert, scaled by E^2); `aux_loss="topk"` is the
top-k form OLMoE/Mixtral train with (HF `load_balancing_loss_func`):
E * sum_i f_i P_i, f_i the share of tokens that have expert i among their
k (so the f_i sum to k).  `z_loss_weight` adds the router z-loss
mean(logsumexp(logits)^2) (ST-MoE; OLMoE trains with 0.001).  Both are
sown as `moe_aux_loss`, which `moe_aux_term`, registered below with
`models/sown.py`, adds to the cross-entropy.

What the layer counts (no token is timed, nothing syncs): each call sows
`moe_tokens_per_expert` (E,) and `moe_dropped` (assignments that reached
no expert: 0 by construction in the grouped path).  The step program
returns them reduced (`collect_moe_stats`, registered below) beside
`loss`, and the Trainer's metrics pump reads them where it already reads
the loss and passes them on as it does any scalar a step counts (log
line, callbacks, a `trainer:step_metrics` span event).

A chip's share of a wider deployment (`experts_held` < `num_experts`):
the layer is told which experts it holds, routes over all of them and
computes its own experts' part of the result on the same grouped path,
with one group a HELD expert (`grouped_experts`); what the absent
experts would have added is left out, and nothing stands in for the
chips that hold them or for the exchange with them.  Such a layer also
sows `moe_rows_held` and `moe_rows_absent` (assignments to experts held
/ not held here); an assignment to an absent expert is not a drop.

An expert is three matrices under a gate — SwiGLU `(silu(x Wg) * (x Wi))
Wd` or ReGLU `(relu(x Wg) * (x Wi)) Wd` — or relu^2's two
(`MoEConfig.expert_act`; the gated forms share every line of the grouped
path, `grouped_experts(gate_act=...)`, and the capacity path knows
SwiGLU alone).  One always-on expert of the routed experts' form may
stand beside them (`shared_width`), and where `shared_gate` is set its
output is multiplied by ONE sigmoid gate a token, sigmoid(x w_s) from a
(hidden, 1) leaf `shared_expert_gate` — product and sigmoid in float32,
under the scope `shared` with the expert it gates; the layer sows the
gate's mean (`moe_shared_gate_mean`).  The router reads the experts' own input unless the block
hands it another (`MoEMLP(x, router_input=h)`: a router placed before
the block's attention reads the block's normalised input; its product
stays under the scope `moe/router`).

Which kernels run the grouped products AND the elementwise passes
between them is `ops/grouped_matmul.experts_route`'s to say, once a
layer call, from the call's shapes, its mesh and the backend: a whole
layer fills its T*k-row buffer and keeps `lax.ragged_dot` (the TPU
compiler's own grouped kernels, 57% of peak on OLMoE's full buffer) and
the `jax.numpy` lines below, which stay the one definition of the
mathematics; a SHARE on one TPU device runs `dwt_gmm` / `dwt_tgmm`,
whose grid walks only the row tiles that hold a held row (the
compiler's kernels walk the whole buffer: at 8 of 128 experts 93% of it
is empty), and `dwt_rows_map_*` over the same tiles for the activation
(and gating product) and its backward, the sum of the two first
products' row gradients, and the combine's backward pair (the weighted
cotangent and <row, cotangent>).  The gathers INTO expert order follow
the held rows too on that route (`dispatch`: a loop over chunks of the
held rows, forward, recomputed and for the combine's cotangent), and so
do the two sums a layer BY ASSIGNMENT (`combine`, and `dispatch`'s
backward): their held entries lie scattered over the (k, T) index list,
so ONE sort of the rows' numbers makes a prefix of them
(`_held_by_token`: the held rows in assignment order, in the place of
the sort that inverts `order`), a loop over that prefix's chunks adds a
token's rows where they lie side by side, and one gather of T entries
reads each token's sum (`_sum_held`): held rows + T index entries where
the gather through the inverse has T*k.  What still walks a share's
whole buffer is the sorts, and nothing else.  The plain route
keeps the gather through the inverse (`_by_assignment`): it is the one
definition of the mathematics, and where every row is held, held rows +
T is more than T*k.

The bookkeeping around the rows — on every route, a whole layer's too —
holds no scatter and no gather of single numbers: on the TPU either
costs the length of its index list whatever an entry weighs (a count of
196,608 assignments into sixteen numbers as long as three sorts of
them).  The assignments to ALL the experts are counted once a layer
call by a compare against `arange(E)` and a sum (`expert_counts`: the
auxiliary term, the bias's rule and the groups' sizes read that one
count); the gates are the scores under a select on the chosen experts
(`route_top_k`: its transpose is a select too); they reach expert order
as a third operand of the sort that makes `order` (`_expert_order`),
and the backward pass's <row, cotangent> numbers return to (T, k) by a
sort ON `order` (`_numbers_by_assignment`), which the compiler folds
into the sort that inverts it where both stand in one pass (the plain
route: two sorts a pass; on the kernel route, where the held rows' sort
stands in the inverse's place, a backward pass runs it as a third).
What is left per T*k entry is those sorts.
A share also sows `moe_gmm_tiles`, `moe_map_tiles` (row tiles its
grouped products / its elementwise passes walk, row tiles of the
buffer), `moe_gather_rows` (rows `dispatch` fetches, rows of the
buffer) and `moe_combine_rows` (index entries a sum by assignment
fetches, T*k), which `collect_moe_stats` reduces to
`moe_gmm_tiles_share`, `moe_map_tiles_share`, `moe_gather_rows_share`
and `moe_combine_rows_share`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.grouped_matmul import (
    experts_route,
    grouped_matmul,
    map_tiles,
    row_tiles,
    rows_map,
    unwritten_rows,
)
from .sown import counters, param_steps, sown, term


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    dtype: Any = jnp.bfloat16
    # "capacity": GShard dense dispatch (einsum, drops overflow tokens).
    #   Its (tokens, E, C) combine tensor and boolean twin grow with
    #   tokens^2 * top_k: at OLMoE's 64 experts top-8 it is 2.7 GB of
    #   float32 at 8,192 tokens and 10.7 GB at 16,384 — past a few
    #   thousand tokens a step only "grouped" exists
    # "grouped": dropless sort + grouped-GEMM via lax.ragged_dot (parity
    #   atorch modules/moe/grouped_gemm_moe.py)
    impl: str = "capacity"
    # renormalise the k chosen gates to sum to 1 (Switch/GShard, Mixtral);
    # False keeps the softmax's own values (OLMoE: `norm_topk_prob: false`)
    # and exists in the grouped path only: MoEMLP refuses it on "capacity"
    norm_topk_prob: bool = True
    # "switch": top-1 fraction x mean prob x E^2 | "topk": HF
    # load_balancing_loss_func, E * sum_i f_i P_i over top-k membership |
    # "none": no auxiliary term is computed or sown (grouped path)
    aux_loss: str = "switch"
    # router z-loss mean(logsumexp(logits)^2); 0 = none
    z_loss_weight: float = 0.0
    # The fields below exist in the grouped path only: MoEMLP refuses
    # every one of them, off its default, on "capacity" (top_k_gating is
    # softmax + renormalised SwiGLU dispatch over all experts, nothing
    # else), as it refuses norm_topk_prob=False there.
    # "softmax" over the router's outputs | "sigmoid" of each (the gates
    # are then normalised by sum + 1e-20 where norm_topk_prob is set)
    score_func: str = "softmax"
    # a (num_experts,) variable `selection_bias` added to the scores for
    # the CHOICE of the k experts only; the gates are the scores without
    # it.  It receives no gradient (it enters through a top-k alone); the
    # model names it in `untrained_params`: the optimizer leaves it alone
    selection_bias: bool = False
    # the out-of-band rule that sets `selection_bias` (auxiliary-loss-free
    # balancing, arXiv:2408.15664, in its error-proportional form): after
    # a step, bias_i += rate * clip((even - load_i) / even, -1, 1), load_i
    # the step's assignments to expert i of ALL num_experts (held here or
    # not) and even their mean.  At most `rate` a step, so an expert with
    # twice its share or none moves as under the sign form, and one near
    # its share hardly moves.  The layer sows that step as
    # `moe_selection_bias_step`; `make_train_step` adds it to the variable
    # (`collect_param_steps`), the optimizer still leaves it alone.
    # 0 = the rule is not run
    bias_update_rate: float = 0.0
    # what a sigmoid router's chosen gates' sum is increased by before it
    # divides them (DeepSeek-V3's published 1e-20; LFM2's 1e-6)
    gate_norm_eps: float = 1e-20
    # the k gates are multiplied by this after normalisation
    routed_scaling: float = 1.0
    # a limit on the groups a token may choose from (DeepSeek-V3's
    # `noaux_tc`): the experts lie in n_group groups of num_experts /
    # n_group, a group's score is the sum of its two largest `probs +
    # bias`, and the k experts come from the topk_group best groups
    # alone (`limit_to_groups`).  1 / 1 = no limit
    n_group: int = 1
    topk_group: int = 1
    # an expert's form: "swiglu" (silu(x Wg) * (x Wi)) Wd | "reglu"
    # (relu(x Wg) * (x Wi)) Wd, the same three matrices under another
    # gate | "relu2" relu(x Wi)^2 Wd, no gate matrix
    expert_act: str = "swiglu"
    # width of one always-on expert beside the routed ones, on every
    # token, in the routed experts' form (`expert_act`: relu2's two
    # matrices, or a gated form's three; scope `shared`); 0 = none
    shared_width: int = 0
    # ONE sigmoid gate a token on the shared expert's output, from a
    # (hidden, 1) leaf `shared_expert_gate`: out += sigmoid(x w_s) *
    # shared(x) (models/qwen3_next.py); False = the shared expert is
    # added as it is.  The layer sows the gate's mean
    shared_gate: bool = False
    # how many of the num_experts this layer holds, experts first_expert
    # .. first_expert + experts_held - 1; 0 = all of them
    experts_held: int = 0
    first_expert: int = 0
    mesh: Any = None  # the model config's (set by auto_accelerate)

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts

    def grouped_only_fields(self) -> dict:
        """The fields the capacity path cannot honour, off their defaults."""
        off = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)
               if f.name in ("score_func", "selection_bias", "routed_scaling",
                             "gate_norm_eps",
                             "bias_update_rate", "n_group", "topk_group",
                             "expert_act", "shared_width", "shared_gate",
                             "experts_held",
                             "first_expert", "norm_topk_prob")
               and getattr(self, f.name) != f.default}
        if self.aux_loss == "none":
            off["aux_loss"] = "none"
        return off


# the gate of a three-matrix expert, by MoEConfig.expert_act
_GATE_ACTS = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}


def top_k_gating(logits: jax.Array, k: int, capacity: int,
                 ) -> Tuple[jax.Array, jax.Array]:
    """Returns (combine (T, E, C), dispatch bool (T, E, C)).

    T tokens, E experts, C capacity per expert.  Tokens beyond an expert's
    capacity are dropped (standard GShard semantics).
    Parity: reference topk_gating.py / switch_gating.py.
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    # iteratively pick top-k experts per token, masking chosen ones with
    # -inf (multiplying probs by 0 re-selects expert 0 when a token's
    # remaining probs underflow to an all-zero row)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    dispatch = jnp.zeros((T, E, capacity), bool)
    masked = probs
    # position counters are computed per expert over the token axis
    fill = jnp.zeros((E,), jnp.int32)
    for _ in range(k):
        choice = jnp.argmax(masked, axis=-1)                    # (T,)
        onehot = jax.nn.one_hot(choice, E, dtype=jnp.int32)     # (T, E)
        # position of each token within its chosen expert's queue
        pos = (jnp.cumsum(onehot, axis=0) - 1) + fill[None, :]  # (T, E)
        fill = fill + onehot.sum(axis=0)
        pos_tok = jnp.take_along_axis(pos, choice[:, None],
                                      axis=1)[:, 0]             # (T,)
        keep = pos_tok < capacity
        gate = jnp.take_along_axis(probs, choice[:, None], axis=1)[:, 0]
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos_tok, capacity),
                                capacity, dtype=jnp.float32)    # (T, C)
        contrib = (onehot.astype(jnp.float32) * gate[:, None]
                   )[:, :, None] * pos_oh[:, None, :]
        combine = combine + jnp.where(keep[:, None, None], contrib, 0.0)
        dispatch = dispatch | (jnp.where(keep[:, None, None], contrib, 0.0)
                               > 0)
        masked = jnp.where(onehot > 0, -jnp.inf, masked)

    # renormalize combine weights over the selected experts (top-k > 1)
    # (the Switch load-balance aux loss lives in MoEMLP, the one place
    # that owns the router probs)
    denom = combine.sum(axis=(1, 2), keepdims=True)
    combine = combine / jnp.where(denom > 0, denom, 1.0)
    return combine, dispatch


def _choice_scores(probs, bias):
    return probs if bias is None else probs + jax.lax.stop_gradient(bias)


def _kept_groups(scores: jax.Array, n_group: int, topk_group: int):
    """(T, n_group) bool: a token's `topk_group` best groups, a group's
    score the sum of its two largest entries.  Two reductions a group
    (the maximum, then the maximum with its first place left out): no
    sort of the group's entries."""
    tokens, experts = scores.shape
    if experts % n_group or not 0 < topk_group <= n_group \
            or experts // n_group < 2:
        raise ValueError(f"{experts} experts in n_group={n_group} groups, "
                         f"topk_group={topk_group} kept")
    grouped = scores.reshape(tokens, n_group, experts // n_group)
    first = jnp.argmax(grouped, axis=-1)[..., None]
    rest = jnp.where(first == jnp.arange(grouped.shape[-1]), -jnp.inf,
                     grouped)
    group_scores = grouped.max(-1) + rest.max(-1)
    _, best = jax.lax.top_k(group_scores, topk_group)
    return (best[..., None] == jnp.arange(n_group)).any(-2)


def limit_to_groups(scores: jax.Array, n_group: int, topk_group: int
                    ) -> jax.Array:
    """`scores` (T, E) with every expert outside the token's `topk_group`
    best groups at -inf: one more `where` before the top-k."""
    kept = _kept_groups(scores, n_group, topk_group)
    return jnp.where(jnp.repeat(kept, scores.shape[-1] // n_group, axis=-1),
                     scores, -jnp.inf)


def group_limit_binds(probs: jax.Array, bias: Optional[jax.Array],
                      experts: jax.Array, n_group: int, topk_group: int
                      ) -> jax.Array:
    """How many tokens' k experts under the group limit differ from the k
    an unlimited choice would take: an expert OUTSIDE the kept groups
    scores over the least of the chosen.  Two fused reductions over (T,
    E), no second top-k."""
    scores = _choice_scores(probs, bias)
    kept = _kept_groups(scores, n_group, topk_group)
    outside = jnp.where(jnp.repeat(kept, scores.shape[-1] // n_group, -1),
                        -jnp.inf, scores).max(-1)
    chosen = experts[..., None] == jnp.arange(probs.shape[-1])
    least = jnp.where(chosen, scores[..., None, :], jnp.inf).min((-2, -1))
    return (outside > least).sum(dtype=jnp.int32)


def route_top_k(probs: jax.Array, top_k: int, norm_topk_prob: bool = True,
                bias: Optional[jax.Array] = None, floor: bool = True,
                scaling: float = 1.0, n_group: int = 1, topk_group: int = 1,
                eps: float = 1e-20) -> Tuple[jax.Array, jax.Array]:
    """(gates (T, k), experts (T, k)) of the k largest router scores —
    of `probs + bias` where a selection bias is given, and inside the
    token's `topk_group` best of `n_group` groups where a group limit is
    (`limit_to_groups`); the gates are `probs` at the chosen experts,
    WITHOUT the bias.  Normalised gates
    are divided by max(sum, 1e-9) (`floor`), or by sum + `eps` (the
    sigmoid router's published form, `MoEConfig.gate_norm_eps`)."""
    scores = _choice_scores(probs, bias)
    if n_group > 1:
        scores = limit_to_groups(scores, n_group, topk_group)
    _, experts = jax.lax.top_k(scores, top_k)
    # `probs` at the chosen experts by a select and a sum over E: a
    # token's k experts are distinct, so it is the number `top_k` or
    # `take_along_axis` hands back, exactly, and its transpose is a dense
    # select where theirs is a scatter-add of T*k numbers into (T, E)
    chosen = experts[..., None] == jnp.arange(probs.shape[-1])
    gates = jnp.where(chosen, probs[..., None, :], 0).sum(-1)
    # the barrier keeps that sum (exact: one term is not zero) a reduce
    # of its own: merged with the sum over k below, as the compiler does
    # under `jit`, the k scores add up in another order and the total's
    # last bit is not `top_k`'s gates' any more (PERF.md section 6, PR 45)
    gates = jax.lax.optimization_barrier(gates)
    if norm_topk_prob:
        total = gates.sum(-1, keepdims=True)
        gates = gates / (jnp.maximum(total, 1e-9) if floor
                         else total + eps)
    if scaling != 1.0:
        gates = gates * scaling
    return gates, experts


def expert_counts(experts: jax.Array, num_experts: int) -> jax.Array:
    """(num_experts,) int32: how many of the (T, k) assignments name each
    expert.  A compare against `arange(num_experts)` and a sum, which the
    compiler fuses (nothing of size T*k*E is written): `jnp.bincount` is
    an integer scatter-add, and on the TPU that costs the length of its
    index list whatever it adds up (PERF.md section 6, PR 45)."""
    hit = experts[..., None] == jnp.arange(num_experts, dtype=experts.dtype)
    return hit.sum(tuple(range(experts.ndim)), dtype=jnp.int32)


def _expert_order(flat_expert: jax.Array, gates: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """(order, the gates in expert order) of the T*k assignments: ONE
    stable sort on the expert's number that carries each assignment's
    own number and its gate along — `argsort(flat_expert)` and
    `gates.reshape(-1)[order]` without the gather of T*k numbers.  The
    gates come out as constants: only a backward pass reads them in that
    order (`_combine_bwd`)."""
    assignment = jnp.arange(flat_expert.shape[0], dtype=jnp.int32)
    _, order, flat_gates = jax.lax.sort(
        (flat_expert, assignment,
         jax.lax.stop_gradient(gates.reshape(-1))), num_keys=1)
    return order, flat_gates


def _numbers_by_assignment(numbers: jax.Array, order: jax.Array,
                           held_rows: jax.Array, k: int) -> jax.Array:
    """numbers (T*k,) in expert order -> (T, k), each assignment's own
    and zero where its row lies behind the held rows: `order` is a
    permutation, so a sort ON it puts row r's number at assignment
    `order[r]` — what `numbers[inv]` gathers one index at a time."""
    held = jnp.arange(order.shape[0]) < held_rows
    _, own = jax.lax.sort((order, jnp.where(held, numbers, 0)), num_keys=1)
    return own.reshape(-1, k)


def _by_assignment(rows: jax.Array, inv: jax.Array,
                   held_rows: jax.Array) -> jax.Array:
    """rows (T*k, d) in expert order -> (k, T, d), each assignment's own
    row: a gather through the sort's inverse, T*k index entries — the
    plain route's, every CPU run's and the parity tests' reference; a
    share on the kernel route sums the same rows from the held ones
    alone (`_sum_held`).  An assignment whose row
    lies behind the held rows (its expert is on another chip) reads
    zeros, whatever that place holds.  The mask comes BEFORE any cast:
    the TPU compiler then fuses cast, weighting and sum into one pass
    over the gathered rows; a cast first is written out at full size
    (3.2 ms a pass at OLMoE's shape, PERF.md section 6, PR 32)."""
    return jnp.where((inv < held_rows)[..., None], rows[inv],
                     jnp.zeros((), rows.dtype))


# rows a turn of `dispatch`'s loop gathers on the "kernel" route: a
# multiple of the kernels' row tile, so the last turn fills the last held
# tile.  More turns cost their overhead, fewer waste half a longer chunk:
# on the chip 4,096 / 8,192 / 16,384 read 39.4 / 38.8 / 39.3 ms a layer
# at 12% of 196,608 rows held, 47.0 / 45.9 / 46.0 at 25% (PERF.md
# section 7, PR 42)
_GATHER_CHUNK = 8192


def _gather_chunk(rows: int) -> int:
    return min(_GATHER_CHUNK, rows)


# rows a turn of a sum by assignment's loop gathers (`_sum_held`): half
# `dispatch`'s, because a turn here holds its chunk three times over —
# the gathered rows, their float32 copy that the k - 1 shifted adds
# read, the rounded sums — and at 8,192 rows of 2,560 numbers the
# gathered rows are no longer staged in VMEM.  On the chip a call at
# 2,048 / 4,096 / 8,192 reads 3.97 / 3.85 / 4.40 ms at SmallThinker's
# shape (49,033 of 196,608 rows held), 2.65 / 2.68 / 2.81 at Kimi's,
# 1.08 / 1.05 / 1.14 at the Nemotron hybrid's (PERF.md section 6, PR 50)
_SUM_CHUNK = 4096


def _sum_chunk(rows: int) -> int:
    return min(_SUM_CHUNK, rows)


def _halo(k: int) -> int:
    """Rows a turn of the sums by assignment reads past its chunk: a
    token's k rows lie side by side in assignment order, so the first of
    them finds the others at most k - 1 places on, in the next turn's
    chunk where the token straddles the edge (rounded up to 16 rows, a
    bfloat16 tile)."""
    return -(-(k - 1) // 16) * 16


def gathered_rows(group_sizes: jax.Array, rows: int,
                  route: str) -> Tuple[jax.Array, jax.Array]:
    """(rows `dispatch` fetches on `route`, rows of the buffer), from
    `group_sizes` alone as `row_tiles` and `map_tiles` are: the held
    rows rounded up to a turn of the loop, or every row where the
    gather is the whole index list's."""
    of = jnp.asarray(rows, jnp.int32)
    if route == "plain":
        return of, of
    chunk = _gather_chunk(rows)
    held = group_sizes.astype(jnp.int32).sum()
    return jnp.minimum(-(-held // chunk) * chunk, of), of


def combined_rows(group_sizes: jax.Array, rows: int, k: int,
                  route: str) -> Tuple[jax.Array, jax.Array]:
    """(index entries ONE sum by assignment fetches on `route`, the T*k
    of the whole list), from `group_sizes` alone as `gathered_rows` is:
    a chunk and its halo a turn of the loop over the held rows and one
    entry a token, or every assignment where the gather is through the
    sort's inverse."""
    of = jnp.asarray(rows, jnp.int32)
    if route == "plain":
        return of, of
    chunk = _sum_chunk(rows)
    held = group_sizes.astype(jnp.int32).sum()
    return -(-held // chunk) * (chunk + _halo(k)) + rows // k, of


class HeldByToken(NamedTuple):
    """The held rows in ASSIGNMENT order (`_held_by_token`): entry i is
    the i-th held assignment, token-major and slot-minor, so a token's
    rows lie side by side.  `token`, `row`, `gate` are (T*k + halo,):
    the assignment's token (T behind the held entries), its row of the
    buffer and its gate; `first` (T,) is where each token's entries
    start, `count` (T,) how many it has."""
    token: jax.Array
    row: jax.Array
    gate: jax.Array
    first: jax.Array
    count: jax.Array


def _held_by_token(order: jax.Array, flat_gates: jax.Array,
                   held: jax.Array) -> HeldByToken:
    """The kernel route's way back from rows to tokens.  `held` (T, k):
    which assignments name a held expert (their rows come first in
    expert order).  ONE sort of the rows' numbers on `order` where a row
    is held and T*k where it is not puts the held rows first, ascending
    by assignment — the inverse's place in the pass, and no gather of
    T*k numbers; a token's entries start at the running sum of the
    tokens' counts before it."""
    t, k = held.shape
    count = held.sum(-1, dtype=jnp.int32)
    row = jnp.arange(t * k, dtype=jnp.int32)
    key = jnp.where(row < count.sum(), order, t * k)
    key, row, gate = jax.lax.sort((key, row, flat_gates), num_keys=1)
    token, row, gate = (
        jnp.pad(x, (0, _halo(k)), constant_values=fill)
        for x, fill in ((key // k, t), (row, 0), (gate, 0)))
    return HeldByToken(token, row, gate, jnp.cumsum(count) - count, count)


def _sum_held(rows: jax.Array, by_token: HeldByToken, held_rows: jax.Array,
              weighted: bool) -> jax.Array:
    """`_by_assignment`'s sum over a token's k rows, from the held rows
    alone: rows (T*k, d) in expert order -> (T, d).  A loop of
    ceil(held_rows / chunk) turns, as `dispatch`'s, of `_SUM_CHUNK` rows:
    a turn gathers a chunk (and its halo) of the buffer's rows in
    assignment order, weighs them in float32 and adds to each entry the
    up to k - 1 that follow it while they are the same token's — at a
    token's FIRST entry that is its rows' sum in ascending slot order,
    the additions `picked.sum(0)` makes over the k slabs in the same
    order (an absent assignment adds an exact zero there) — rounds it
    once and writes the chunk in place.  One gather of T entries then
    reads each token's sum at its first entry; a token with none reads
    zero."""
    t = by_token.first.shape[0]
    k = rows.shape[0] // t
    chunk, halo = _sum_chunk(rows.shape[0]), _halo(k)

    def turn(c, buffer):
        # a last turn that would pass the buffer's end is moved back onto
        # it by the slices and by the update alike, as `dispatch`'s is
        # (the lists are a halo longer than the buffer and so is a
        # slice: both clamp their start to rows - chunk); what it sums
        # twice is the same
        at = c * chunk
        token, row, gate = (
            jax.lax.dynamic_slice(x, (at,), (chunk + halo,))
            for x in (by_token.token, by_token.row, by_token.gate))
        picked = rows[row].astype(jnp.float32)
        if weighted:
            picked = picked * gate[:, None]
        total = picked[:chunk]
        for s in range(1, k):
            same = token[s:s + chunk] == token[:chunk]
            total = total + jnp.where(same[:, None], picked[s:s + chunk], 0)
        return jax.lax.dynamic_update_slice(
            buffer, total.astype(rows.dtype), (at, 0))

    sums = jax.lax.fori_loop(0, -(-held_rows // chunk), turn,
                             unwritten_rows(rows.shape[0], rows))
    return jnp.where((by_token.count > 0)[:, None], sums[by_token.first],
                     jnp.zeros((), rows.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def dispatch(tokens: jax.Array, order: jax.Array, by_token,
             held_rows: jax.Array, route: str = "plain") -> jax.Array:
    """tokens (T, d) -> rows (T*k, d) in expert order: row r is the
    token of assignment `order[r]`, token `order[r] // k`.  `held_rows`:
    how many rows belong to a group.  `by_token` is the way back, which
    the backward pass takes: on "plain" `inv` (k, T), the inverse of
    `order` — `inv[j, t]` is the row of assignment t*k + j (k leads, so
    that a sum over a token's k rows adds k whole (T, d) slabs); on
    "kernel" a `HeldByToken`.

    `route` is the layer's (`grouped_experts`).  On "plain" every row is
    gathered, `tokens[order // k]`: the one definition of the
    mathematics.  On "kernel" nothing reads a row tile behind the held
    rows, and a row gather from HBM costs the length of its index list
    whatever a row weighs, so the buffer is filled by a loop of
    ceil(held_rows / chunk) turns — a dynamic trip count, as the
    kernels' grids are — each turn the same gather over `_GATHER_CHUNK`
    entries of `order`, written in place.  The rows behind the last turn
    are never written: they hold whatever `unwritten_rows` left there.

    `dispatch` and `combine` are each other's transposes and each one's
    backward pass is the other, so rows move by gathers in both
    directions: a token's k rows are found (through `inv`, or side by
    side among the held rows in assignment order) and summed, none is
    scattered.  A backward pass is traced under the named scopes of the
    forward call, so its instructions keep the caller's scope."""
    k = order.shape[0] // tokens.shape[0]
    if route != "kernel":
        return tokens[order // k]
    rows, chunk = order.shape[0], _gather_chunk(order.shape[0])

    def turn(c, buffer):
        # a last turn that would pass the buffer's end is moved back onto
        # it by the slice and by the update alike (both clamp their
        # start): what it gathers twice is the same
        idx = jax.lax.dynamic_slice(order, (c * chunk,), (chunk,)) // k
        return jax.lax.dynamic_update_slice(buffer, tokens[idx],
                                            (c * chunk, 0))

    return jax.lax.fori_loop(0, -(-held_rows // chunk), turn,
                             unwritten_rows(rows, tokens))


def _dispatch_fwd(tokens, order, by_token, held_rows, route):
    return (dispatch(tokens, order, by_token, held_rows, route),
            (order, by_token, held_rows))


def _dispatch_bwd(route, res, d_rows):
    return combine(d_rows, None, None, *res, route), None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def combine(rows: jax.Array, gates: Optional[jax.Array],
            flat_gates: Optional[jax.Array], order: jax.Array,
            by_token, held_rows: jax.Array,
            route: str = "plain") -> jax.Array:
    """rows (T*k, d) in expert order, gates (T, k) or None (all ones) ->
    (T, d): the sum of each token's k rows, weighted in the same pass,
    accumulated in float32 (`dispatch` says what `order`, `by_token` and
    `held_rows` are).  `flat_gates` (T*k,) is the gates once more, in
    expert order (`_expert_order`; None with `gates`): the backward pass
    weighs the rows' cotangent with them where the rows lie, and they
    get no gradient of their own.

    `route` is the layer's (`grouped_experts`).  On "plain" the rows are
    gathered through `inv`, all T*k of them (`_by_assignment`): the one
    definition of the mathematics, and where every row is held the
    shortest index list there is.  On "kernel" the sum reads the held
    rows alone (`_sum_held`: held rows + T index entries, the same
    additions in the same order), and the backward pass's two passes
    over the row buffer are one `rows_map` over the held tiles."""
    if route == "kernel":
        return _sum_held(rows, by_token, held_rows, gates is not None)
    picked = _by_assignment(rows, by_token, held_rows).astype(jnp.float32)
    if gates is not None:
        picked = picked * gates.T[..., None]
    return picked.sum(0).astype(rows.dtype)


def _combine_fwd(rows, gates, flat_gates, order, by_token, held_rows, route):
    return (combine(rows, gates, flat_gates, order, by_token, held_rows,
                    route),
            (rows, gates, flat_gates, order, by_token, held_rows))


def _weigh(rows, d_rows, gates):
    """`_combine_bwd`'s two passes on blocks: the cotangent weighted (a
    float32 product, rounded once) and <row, cotangent> a row."""
    dots = (rows.astype(jnp.float32) * d_rows).sum(-1, keepdims=True)
    return (d_rows * gates).astype(rows.dtype), dots


def _combine_bwd(route, res, d_out):
    rows, gates, flat_gates, order, by_token, held_rows = res
    d_rows = dispatch(d_out, order, by_token, held_rows, route)
    if gates is None:
        return d_rows, None, None, None, None, None
    if route == "kernel":
        # both passes in one kernel over the tiles that hold a held row,
        # the weighted cotangent written over the gathered one
        d_rows, dots = rows_map(_weigh, held_rows, rows, d_rows,
                                flat_gates[:, None], alias=(1, 0))
        dots = dots[:, 0]
    else:
        # <row, its token's cotangent> in expert order, where both lie
        # (a pass over the buffer, no second gather of rows)
        dots = (rows.astype(jnp.float32) * d_rows).sum(-1)
        d_rows = (d_rows * flat_gates[:, None]).astype(rows.dtype)
    # back to (T, k) as T*k numbers
    d_gates = _numbers_by_assignment(dots, order, held_rows, gates.shape[1])
    return d_rows, d_gates.astype(gates.dtype), None, None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)


@functools.lru_cache(maxsize=None)
def _activation(gate_act):
    """An expert's activation as a function of the first products'
    blocks (`rows_map` wants one object a form, whatever the layer):
    relu^2 of the one product where there is no gate, else `gate_act` of
    the gate matrix's times the other's."""
    if gate_act is None:
        def act(a):
            return (jnp.square(jax.nn.relu(a)),)
        act.__name__ = "relu2"
    else:
        def act(g, u):
            return (gate_act(g) * u,)
        act.__name__ = f"gated_{gate_act.__name__}"
    return act


def grouped_experts(tokens: jax.Array, gates: jax.Array, experts: jax.Array,
                    w_gate: Optional[jax.Array], w_in: jax.Array,
                    w_down: jax.Array, first_expert: int = 0,
                    num_experts: Optional[int] = None, mesh=None,
                    gate_act=jax.nn.silu, load: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """The dropless expert pass for a routing already made: returns
    (out (T, d), group_sizes (held,)).  Scopes: `dispatch` (sort, its
    inverse — on the kernel route the held rows in assignment order —
    gather), `experts` (grouped matmuls, gating product), `combine`
    (the rows back by assignment, weighting, sum over k), and their
    backward passes under the same two (`dispatch`, `combine` above).
    `w_gate=None`: relu^2 experts; else `gate_act` of the gate matrix's
    product times the other's (silu: SwiGLU, relu: ReGLU).
    `load`: `expert_counts(experts, num_experts)` where the caller has
    counted already (`MoEMLP`: once a layer call), else counted here;
    the groups' sizes are the held experts' part of it.

    The weights hold `held = w_in.shape[0]` experts, numbers
    `first_expert ..` of the `num_experts` that `experts` (T, k) names.
    Fewer than all of them is a chip's share: an assignment to an absent
    expert is taken out BEFORE the sort.  It gets no group (`group_sizes`
    has one entry a held expert) and its place in the static (T*k)-row
    buffer lies behind every held row.  No group of a grouped product
    writes those places, and what the TPU's grouped kernels leave there
    is not zero (a NaN by step 20, PERF.md section 6, PR 31).  Two
    things keep it out of every result and gradient.  On the "plain"
    route (`lax.ragged_dot`) the first product's places behind the held
    rows are set to zero (so the activation's are, and by the mask's
    transpose those of its cotangent); on the "kernel" route (`route`,
    decided ONCE here for the products and the passes between them: a
    share on one TPU device, `mesh` None or of size 1) nothing reads or
    writes a row tile behind the held rows at all — `dispatch` gathers
    the held rows' chunks alone and leaves the buffer behind them as it
    found it, the products' kernels and the maps' visit the tiles that
    hold a held row, and a map zeroes the rest of the last one.  And on
    either route the sums over a token's k rows take in no place behind
    the held rows, be it of the last product or of the rows' own
    gradient: on "plain" a mask over the gather of all T*k assignments
    (`_by_assignment`), on "kernel" a sum over the held rows in
    assignment order, which indexes no other (`_sum_held`; a last
    turn's chunk may pass them, and a select keeps what it fetched
    there out of every token's sum)."""
    T, top_k = experts.shape
    E = w_in.shape[0]
    share = num_experts is not None and E < num_experts
    route = experts_route(T * top_k, (w_gate, w_in, w_down), num_experts,
                          mesh)
    with jax.named_scope("dispatch"):
        flat_expert = experts.reshape(-1)              # (T*k,)
        if share:
            flat_expert = flat_expert - first_expert
            flat_expert = jnp.where(
                (flat_expert >= 0) & (flat_expert < E), flat_expert, E)
        order, flat_gates = _expert_order(flat_expert, gates)
        if route == "kernel":
            by_token = _held_by_token(order, flat_gates,
                                      flat_expert.reshape(T, top_k) < E)
        else:
            # the inverse of a permutation is its argsort: T*k integers
            # (on the chip a third of what scattering them costs, PERF.md
            # PR 32)
            by_token = jnp.argsort(order).reshape(T, top_k).T
        if load is None:
            load = expert_counts(experts, num_experts or E)
        group_sizes = load[first_expert:first_expert + E]
        held_rows = group_sizes.sum()
        held_row = jnp.arange(T * top_k) < held_rows
        xs = dispatch(tokens, order, by_token, held_rows, route)
        xs = xs.astype(w_in.dtype)                     # (T*k, d) sorted

    def grouped(lhs, rhs):
        out = grouped_matmul(lhs, rhs, group_sizes)
        return jnp.where(held_row[:, None], out, 0) if share else out

    with jax.named_scope("experts"):
        if route == "kernel":
            # no mask over the buffer: the activation's kernel zeroes
            # what the last held tile holds behind the held rows and
            # visits no tile behind it, forward and backward
            firsts = (w_in,) if w_gate is None else (w_gate, w_in)
            h, = rows_map(_activation(None if w_gate is None else gate_act),
                          held_rows,
                          *grouped_matmul(xs, firsts, group_sizes, route))
        elif w_gate is None:  # relu^2: no gate matrix
            h = jnp.square(jax.nn.relu(grouped(xs, w_in)))
        else:
            h = gate_act(grouped(xs, w_gate)) * grouped(xs, w_in)
        # (T*k, d); no mask here: `combine` reads the held rows alone
        ys = grouped_matmul(h, w_down, group_sizes, route)
    with jax.named_scope("combine"):
        out = combine(ys, gates, flat_gates, order, by_token, held_rows,
                      route)
    return out.astype(tokens.dtype), group_sizes


def grouped_moe(tokens: jax.Array, probs: jax.Array, w_gate: jax.Array,
                w_in: jax.Array, w_down: jax.Array, top_k: int,
                norm_topk_prob: bool = True) -> jax.Array:
    """Dropless MoE via sort + grouped GEMM (`jax.lax.ragged_dot`).

    Parity: reference `atorch/atorch/modules/moe/grouped_gemm_moe.py` —
    tokens sorted by expert, one grouped matmul per projection, no
    capacity limit so nothing is dropped.

    What a v5e trace showed (jax 0.9.0, OLMoE's 163,840 rows in 64
    groups of 2048 x 1024; PERF.md, PR 26 and PR 32): the TPU compiler
    puts grouped-matmul kernels of its own in place of each `ragged_dot`
    (`ragged-dot-none.N`, nine a step: three forward, their six
    transposes), 5.9-6.5 ms each, together 57% of the bf16 peak.  The
    top-k and the two sorts are cheap (under 0.5 ms together), and so is
    the gather into expert order (1.0 ms: its 84 MB source is staged in
    fast memory).  What costs is moving the 671 MB row buffer back: as
    SCATTER-ADDS (`segment_sum` in the combine, the transpose of the
    gather in the backward pass) 12 ms each, as long as two of the
    grouped matmuls; as the gathers through the sort's inverse that
    `dispatch` and `combine` are now, 5.6 ms each (a row gather from
    HBM runs at 34 ns a 4 KB row) and 1.0 ms for the sum over k.  An
    index costs a few nanoseconds whatever it moves, so the single
    numbers around the rows are not indexed at all (PR 45): the group
    sizes are a compare-and-sum (`expert_counts`; as a `bincount` 1.4
    ms), the gates in expert order and their gradient's dots ride the
    sorts (as gathers of T*k numbers 1.2 and 1.4 ms).

    tokens (T, d); probs (T, E) router softmax; w_gate/w_in (E, d, f);
    w_down (E, f, d).  Returns (T, d).
    """
    gates, experts = route_top_k(probs, top_k, norm_topk_prob)
    return grouped_experts(tokens, gates, experts, w_gate, w_in, w_down)[0]


def _aux_loss(cfg: MoEConfig, logits, probs, load):
    """The layer's auxiliary loss terms, weighted, as one scalar; `load`
    is `expert_counts` of the step's routing (the "topk" term reads it)."""
    E = cfg.num_experts
    if cfg.aux_loss == "topk":
        # f_i: share of tokens with expert i among their k (sums to k)
        f = load.astype(jnp.float32) / probs.shape[0]
        aux = (f * probs.mean(0)).sum() * E
    elif cfg.aux_loss == "switch":
        top1 = jax.nn.one_hot(jnp.argmax(probs, -1), E, dtype=jnp.float32)
        aux = (top1.mean(0) * probs.mean(0)).sum() * E ** 2
    else:
        raise ValueError(f"unknown MoEConfig.aux_loss {cfg.aux_loss!r}")
    aux = aux * cfg.aux_loss_weight
    if cfg.z_loss_weight:
        z = jax.scipy.special.logsumexp(logits, axis=-1)
        aux = aux + cfg.z_loss_weight * jnp.mean(z * z)
    return aux


class MoEMLP(nn.Module):
    """Drop-in MLP replacement: router + stacked experts (SwiGLU or
    ReGLU, or relu^2 without a gate matrix), and where `shared_width` is
    set one always-on expert of the same form beside them — under ONE
    sigmoid gate a token where `shared_gate` is set.  The router reads the
    experts' own input unless it is handed another (`router_input`: a
    block whose router sits before its attention).

    Expert weights are (held, d, h)/(held, h, d) so the `ep` mesh axis
    shards the leading dim (MOE_RULES in parallel/sharding.py);
    dispatch/combine einsums let GSPMD place the all-to-alls on ICI.

    Scopes in the compiled step (analysis/hlo_scopes.py): everything under
    `moe`; `router` (the flax Dense's own name), `aux`, `shared`, and in
    the grouped path `dispatch`, `experts`, `combine`.
    """

    hidden: int
    ffn: int
    moe: MoEConfig

    # keep it one method: flax names a helper method's ops
    # `<module>._helper`, in the middle of the scopes above
    @nn.compact
    @jax.named_scope("moe")
    def __call__(self, x, router_input=None):  # both (B, T, d)
        cfg = self.moe
        if cfg.impl != "grouped" and cfg.grouped_only_fields():
            raise ValueError(
                f"MoEConfig fields {cfg.grouped_only_fields()} need "
                f"impl='grouped': top_k_gating is a softmax router with "
                f"renormalised gates over SwiGLU experts that are all "
                f"held, and nothing else")
        if cfg.shared_gate and not cfg.shared_width:
            raise ValueError("shared_gate gates the shared expert: there "
                             "is none")
        if cfg.bias_update_rate and not cfg.selection_bias:
            raise ValueError("bias_update_rate sets the selection bias: "
                             "there is none")
        B, T, d = x.shape
        tokens = x.reshape(B * T, d)
        n_tok = B * T
        capacity = max(1, int(cfg.capacity_factor * n_tok * cfg.top_k
                              / cfg.num_experts))

        router = nn.Dense(cfg.num_experts, use_bias=False,
                          dtype=jnp.float32, name="router")
        routed_on = tokens if router_input is None \
            else router_input.reshape(B * T, d)
        logits = router(routed_on.astype(jnp.float32))

        w_in = self.param(
            "experts_w_in", nn.initializers.normal(0.02),
            (cfg.held, d, self.ffn)).astype(cfg.dtype)
        w_gate = None
        if cfg.expert_act in _GATE_ACTS:
            w_gate = self.param(
                "experts_w_gate", nn.initializers.normal(0.02),
                (cfg.held, d, self.ffn)).astype(cfg.dtype)
        elif cfg.expert_act != "relu2":
            raise ValueError(f"unknown MoEConfig.expert_act "
                             f"{cfg.expert_act!r}")
        w_out = self.param(
            "experts_w_down", nn.initializers.normal(0.02),
            (cfg.held, self.ffn, d)).astype(cfg.dtype)
        bias = None
        if cfg.selection_bias:
            # small and not zero, so that a seeded model's choice of
            # experts differs from its scores' order
            bias = self.param("selection_bias",
                              nn.initializers.normal(0.02),
                              (cfg.num_experts,))

        with jax.named_scope("router"):
            if cfg.score_func == "softmax":
                probs = jax.nn.softmax(logits, axis=-1)
            elif cfg.score_func == "sigmoid":
                probs = jax.nn.sigmoid(logits)
            else:
                raise ValueError(f"unknown MoEConfig.score_func "
                                 f"{cfg.score_func!r}")
        gates = experts = load = None
        if cfg.impl == "grouped" or cfg.aux_loss == "topk":
            with jax.named_scope("dispatch"):
                # a router without a group limit is called as it always
                # was (tests stand the parent's `route_top_k` in its place)
                limit = dict(n_group=cfg.n_group, topk_group=cfg.topk_group
                             ) if cfg.n_group > 1 else {}
                eps = {} if cfg.gate_norm_eps == MoEConfig.gate_norm_eps \
                    else dict(eps=cfg.gate_norm_eps)
                gates, experts = route_top_k(
                    probs, cfg.top_k, cfg.norm_topk_prob, bias=bias,
                    floor=cfg.score_func == "softmax",
                    scaling=cfg.routed_scaling, **limit, **eps)
                if limit:
                    # counted, not timed: tokens the limit moved, tokens
                    self.sow("intermediates", "moe_group_limit", jnp.stack([
                        group_limit_binds(probs, bias, experts, cfg.n_group,
                                          cfg.topk_group),
                        jnp.int32(n_tok)]))
                # the step's assignments to each of ALL the experts,
                # counted once: the auxiliary term, the bias's rule and
                # the groups' sizes read it
                load = expert_counts(experts, cfg.num_experts)
        if cfg.aux_loss != "none":
            with jax.named_scope("aux"):
                self.sow("intermediates", "moe_aux_loss",
                         _aux_loss(cfg, logits, probs, load))
        if cfg.bias_update_rate:
            with jax.named_scope("dispatch"):
                even = n_tok * cfg.top_k / cfg.num_experts
                self.sow("intermediates", "moe_selection_bias_step",
                         cfg.bias_update_rate * jnp.clip(
                             (even - load) / even, -1.0, 1.0))

        if cfg.impl == "grouped":
            out, counts = grouped_experts(
                tokens, gates, experts, w_gate, w_in, w_out,
                first_expert=cfg.first_expert, num_experts=cfg.num_experts,
                mesh=cfg.mesh,
                gate_act=_GATE_ACTS.get(cfg.expert_act, jax.nn.silu),
                load=load)
        else:
            combine, dispatch = top_k_gating(logits, cfg.top_k, capacity)
            # dispatch: (T, E, C) x (T, d) -> (E, C, d)
            xe = jnp.einsum("tec,td->ecd", dispatch.astype(cfg.dtype),
                            tokens.astype(cfg.dtype))
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) * \
                jnp.einsum("ecd,edf->ecf", xe, w_in)
            ye = jnp.einsum("ecf,efd->ecd", h, w_out)
            # combine back: (T, E, C) x (E, C, d) -> (T, d)
            out = jnp.einsum("tec,ecd->td", combine.astype(cfg.dtype), ye)
            counts = dispatch.sum(axis=(0, 2), dtype=jnp.int32)
        # counted, not timed: rows each expert ran, and the assignments
        # (token, one of its k) that reached none
        self.sow("intermediates", "moe_tokens_per_expert", counts)
        dropped = n_tok * cfg.top_k - counts.sum()
        if cfg.held < cfg.num_experts:
            # the rest of the token's k went to experts on other chips:
            # counted, and not a drop
            self.sow("intermediates", "moe_rows_held", counts.sum())
            self.sow("intermediates", "moe_rows_absent", dropped)
            dropped = jnp.zeros((), dropped.dtype)
            # how far the grouped products, the elementwise passes
            # between them, the gathers into expert order and the sums
            # by assignment follow the held rows: row tiles (rows, index
            # entries) they walk, of the buffer's (no sync)
            rows = n_tok * cfg.top_k
            route = experts_route(rows, (w_gate, w_in, w_out),
                                  cfg.num_experts, cfg.mesh)
            self.sow("intermediates", "moe_gmm_tiles",
                     jnp.stack(row_tiles(counts, rows, route)))
            self.sow("intermediates", "moe_map_tiles",
                     jnp.stack(map_tiles(counts, rows, route)))
            self.sow("intermediates", "moe_gather_rows",
                     jnp.stack(gathered_rows(counts, rows, route)))
            self.sow("intermediates", "moe_combine_rows",
                     jnp.stack(combined_rows(counts, rows, cfg.top_k,
                                             route)))
        self.sow("intermediates", "moe_dropped", dropped)
        if cfg.shared_width:
            with jax.named_scope("shared"):
                dense = functools.partial(nn.Dense, use_bias=False,
                                          dtype=cfg.dtype)
                h = dense(cfg.shared_width, name="shared_up_proj")(tokens)
                if w_gate is None:
                    h = jnp.square(jax.nn.relu(h))
                else:  # the routed experts' form: a third matrix, gated
                    h = _GATE_ACTS[cfg.expert_act](dense(
                        cfg.shared_width, name="shared_gate_proj")(tokens)) * h
                shared = dense(d, name="shared_down_proj")(h)
                if cfg.shared_gate:
                    # one number a token, float32 from the product on
                    gate = jax.nn.sigmoid(nn.Dense(
                        1, use_bias=False, dtype=jnp.float32,
                        name="shared_expert_gate")(
                            tokens.astype(jnp.float32)))
                    self.sow("intermediates", "moe_shared_gate_mean",
                             jax.lax.stop_gradient(gate.mean()))
                    shared = (shared * gate).astype(shared.dtype)
                out = out + shared
        return out.reshape(B, T, d)


def collect_moe_aux_loss(intermediates) -> jax.Array:
    """Sum only the sown `moe_aux_loss` leaves of an intermediates
    collection — any other sown diagnostic (attention stats, logging
    metrics) must not silently become a loss term."""
    total = jnp.zeros((), jnp.float32)
    for leaf in sown(intermediates, "moe_aux_loss"):
        total = total + jnp.sum(leaf)
    return total


@term
def moe_aux_term(intermediates, batch, ce):
    """`collect_moe_aux_loss` as the loss's first term, or None where no
    layer sowed one: the step's program is then the same whether or not
    this module was ever imported."""
    if not list(sown(intermediates, "moe_aux_loss")):
        return None
    return collect_moe_aux_loss(intermediates), {}


@param_steps
def collect_param_steps(intermediates) -> Dict[str, Any]:
    """The steps the layers of one forward pass ask for on variables the
    optimizer leaves alone, as a tree shaped like the part of `params`
    they touch ({} where no layer runs such a rule): today each sown
    `moe_selection_bias_step` goes to its own layer's `selection_bias`
    (MoEConfig.bias_update_rate)."""
    steps: Dict[str, Any] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        keys = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        if "moe_selection_bias_step" not in keys:
            continue
        node = steps
        for key in keys[:keys.index("moe_selection_bias_step")]:
            node = node.setdefault(key, {})
        node["selection_bias"] = leaf
    return steps


@counters
def collect_moe_stats(intermediates) -> Dict[str, jax.Array]:
    """What the MoE layers of one forward pass counted, reduced to
    scalars — or {} for a model with no such layer: the worst layer's
    `max_i(tokens_i) / mean_i(tokens_i)`, the dropped assignments of all
    layers, and where the layers hold a share of their experts the
    assignments to experts held / not held here, all layers, and the
    shares of the row buffers' tiles their grouped products and their
    elementwise passes walk, of their rows `dispatch` fetches and of
    their assignments the sums by assignment index; under a group limit
    `moe_group_limit_binds`, the share of tokens (all layers) whose k
    experts differ from the k an unlimited choice would take; where the
    shared experts are gated (`MoEConfig.shared_gate`)
    `moe_shared_gate_mean`, the gates' mean over tokens and layers."""
    counts = [n.astype(jnp.float32)
              for n in sown(intermediates, "moe_tokens_per_expert")]
    if not counts:
        return {}
    loads = [n.max() / jnp.maximum(n.mean(), 1.0) for n in counts]
    sums = {name: [jnp.sum(v) for v in sown(intermediates, name)]
            for name in ("moe_dropped", "moe_rows_held", "moe_rows_absent")}
    stats = {"moe_load_max_over_mean": jnp.max(jnp.stack(loads)),
             **{name: jnp.sum(jnp.stack(v)) for name, v in sums.items()
                if v}}
    for name, stat in (("moe_gmm_tiles", "moe_gmm_tiles_share"),
                       ("moe_map_tiles", "moe_map_tiles_share"),
                       ("moe_gather_rows", "moe_gather_rows_share"),
                       ("moe_combine_rows", "moe_combine_rows_share"),
                       ("moe_group_limit", "moe_group_limit_binds")):
        tiles = [v.reshape(-1, 2) for v in sown(intermediates, name)]
        if tiles:
            walked, of = jnp.concatenate(tiles).astype(jnp.float32).sum(0)
            stats[stat] = walked / of
    gates = [v.reshape(()) for v in sown(intermediates,
                                         "moe_shared_gate_mean")]
    if gates:
        stats["moe_shared_gate_mean"] = jnp.stack(gates).mean()
    return stats
