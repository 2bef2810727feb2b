"""HLO scope table (analysis/hlo_scopes.py): op_name normalisation and
fusion resolution on hand-written HLO, then the repo's REAL
`make_train_step` lowered for a 2-layer GPT (as test_hlo_budget.py
lowers it): every matmul of the step lands in a named part, the loss is
named forward and backward, and the optimizer's ops carry its scope.
"""

import re

import pytest

from dlrover_wuqiong_tpu.analysis.hlo_budget import iter_collectives
from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
    instructions_of,
    moved_bytes,
    owners,
    parse_computations,
    relayouts,
    scope_of,
    scope_table,
    shape_bytes,
)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp(GPT)/h_3/mlp/c_fc/dot_general",
     "fwd/GPT/h/mlp/c_fc"),
    ("jit(train_step)/transpose(jvp(GPT))/h_11/attn/c_attn/dot_general",
     "bwd/GPT/h/attn/c_attn"),
    ("jit(train_step)/transpose(jvp(GPT))/jvp(GPT)/checkpoint/"
     "rematted_computation/h_0/mlp/c_proj/dot_general",
     "recompute/GPT/h/mlp/c_proj"),
    # backward of a checkpointed block: `checkpoint` alone is a wrapper
    ("jit(train_step)/transpose(jvp(GPT))/jvp(GPT)/checkpoint/h_0/ln_1/"
     "mul", "bwd/GPT/h/ln_1"),
    # the tracer names the differentiated function once a transform: the
    # repeat goes, under the accumulation loop too
    ("jit(train_step)/accum/while/body/closed_call/transpose(jvp(GPT))/"
     "jvp(GPT)/checkpoint/rematted_computation/h_1/attn/c_proj/add",
     "recompute/accum/GPT/h/attn/c_proj"),
    ("jit(train_step)/transpose(jvp(NemotronH))/jvp(NemotronH)/checkpoint/"
     "layers_3/norm/mul", "bwd/NemotronH/layers/norm"),
    # a module that holds one of its own name is a path, not a repeat
    ("jit(train_step)/jvp(GPT)/h_0/mlp/mlp/add", "fwd/GPT/h/mlp/mlp"),
    # names the compiler joined: what the pieces agree on
    ("jit(train_step)/jvp(GPT)/h_0/mlp/c_fc/dot_general;"
     "jit(train_step)/jvp(GPT)/h_0/mlp/c_proj/add", "fwd/GPT/h/mlp"),
    ("jit(train_step)/jvp(GPT)/h_0/mlp/c_fc/mul;"
     "jit(train_step)/transpose(jvp(GPT))/h_0/mlp/c_fc/mul",
     "GPT/h/mlp/c_fc"),
    ("jit(train_step)/jvp(GPT)/head/bte,ve->btv/dot_general",
     "fwd/GPT/head/bte,ve->btv"),
    ("jit(train_step)/jvp(loss)/jit(take_along_axis)/gather", "fwd/loss"),
    ("jit(train_step)/transpose(jvp(loss))/jit(_one_hot)/eq", "bwd/loss"),
    ("jit(train_step)/optimizer/mul", "optimizer"),
    ("jit(train_step)/accum/while/body/closed_call/jvp(GPT)/h_1/attn/"
     "c_proj/add", "fwd/accum/GPT/h/attn/c_proj"),
    ("jit(fused_train_step)/while/body/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/jvp(Llama)/layers_7/mlp/gate_proj/dot_general",
     "fwd/Llama/layers/mlp/gate_proj"),
    # a jit frame last: nothing to drop as the primitive
    ("jit(train_step)/jvp(GPT)/wte/jit(_take)", "fwd/GPT/wte"),
    ("jit(train_step)/add", ""),
    ("", ""),
])
def test_scope_normalisation(op_name, scope):
    assert scope_of(op_name) == scope


_HAND_HLO = """\
HloModule jit_train_step, entry_computation_layout={()->f32[]}

%fused_computation.1 (p0: bf16[8,64], p1: bf16[64,256]) -> bf16[8,256] {
  %p0 = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[64,256]{1,0:T(8,128)(2,1)} parameter(1)
  %convert.3 = bf16[8,64]{1,0} convert(%p0), metadata={op_name="jit(train_step)/jvp(GPT)/h_0/ln_2/mul"}
  ROOT %convolution.1 = bf16[8,256]{1,0:T(8,128)(2,1)} convolution(%convert.3, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/jvp(GPT)/h_0/mlp/c_fc/dot_general" stack_frame_id=7}
}

%fused_computation.2 (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %multiply.1 = f32[8]{0} multiply(%p0, %p1), metadata={op_name="jit(train_step)/jvp(GPT)/h_1/mlp/mul"}
  ROOT %add.9 = f32[8]{0} add(%multiply.1, %p1), metadata={op_name="jit(train_step)/transpose(jvp(GPT))/h_1/mlp/c_proj/add_any"}
}

%fused_computation.3 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %negate.1 = f32[8]{0} negate(%p0), metadata={op_name="jit(train_step)/transpose(jvp(GPT))/h_0/ln_1/neg"}
  ROOT %exp.1 = f32[8]{0} exponential(%negate.1), metadata={op_name="jit(train_step)/transpose(jvp(loss))/exp"}
}

%fused_computation.4 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %convert.8 = f32[8]{0} convert(%p0), metadata={op_name="jit(train_step)/transpose(jvp(GPT))/h_0/mlp/c_fc/reduce_sum"}
  %multiply.8 = f32[8]{0} multiply(%convert.8, %convert.8), metadata={op_name="jit(train_step)/optimizer/mul"}
  ROOT %subtract.8 = f32[8]{0} subtract(%p0, %multiply.8), metadata={op_name="jit(train_step)/optimizer/sub"}
}

ENTRY %main.42 (Arg_0.1: bf16[8,64], Arg_1.2: bf16[64,256]) -> (bf16[8,256], f32[8]) {
  %Arg_0.1 = bf16[8,64]{1,0} parameter(0), metadata={op_name="batch['input_ids']"}
  %Arg_1.2 = bf16[64,256]{1,0} parameter(1)
  %fusion.7 = bf16[8,256]{1,0:T(8,128)(2,1)} fusion(%Arg_0.1, %Arg_1.2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(GPT)/h_0/ln_2/mul"}
  %copy.5 = bf16[8,256]{0,1} copy(%fusion.7)
  %multiply_add_fusion = f32[8]{0} fusion(%copy.5, %copy.5), kind=kLoop, calls=%fused_computation.2
  %fusion.9 = f32[8]{0} fusion(%multiply_add_fusion), kind=kLoop, calls=%fused_computation.3
  %fusion.11 = f32[8]{0} fusion(%fusion.9), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(train_step)/optimizer/sub"}
  %all-reduce-start.1 = (f32[8]{0}, f32[8]{0}) all-reduce-start(%fusion.9, %fusion.9), replica_groups={}, metadata={op_name="jit(train_step)/optimizer/reduce_sum"}
  %dwt_fa_fwd.3 = bf16[8,256]{1,0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(GPT)/h_0/attn/pallas_call"}
  ROOT %tuple.1 = (bf16[8,256]{1,0}, f32[8]{0}) tuple(%dwt_fa_fwd.3, %fusion.9)
}
"""


def test_parser_reads_opcodes_past_tpu_layouts_and_tuples():
    comps = parse_computations(_HAND_HLO)
    assert set(comps) == {"fused_computation.1", "fused_computation.2",
                          "fused_computation.3", "fused_computation.4",
                          "main.42"}
    entry = {i["name"]: i for i in comps["main.42"]}
    assert entry["fusion.7"]["opcode"] == "fusion"
    assert entry["fusion.7"]["calls"] == "fused_computation.1"
    assert entry["all-reduce-start.1"]["opcode"] == "all-reduce-start"
    assert entry["dwt_fa_fwd.3"]["opcode"] == "custom-call"
    assert entry["tuple.1"]["opcode"] == "tuple"
    assert entry["Arg_0.1"]["op_name"] == "batch['input_ids']"
    inner = {i["name"]: i for i in comps["fused_computation.1"]}
    assert inner["convolution.1"]["opcode"] == "convolution"


def test_a_fusion_takes_its_matmul_else_the_common_prefix():
    table = scope_table(_HAND_HLO)
    # the convolution decides, not the fusion's own (root) metadata
    assert table["fusion.7"] == "fwd/GPT/h/mlp/c_fc"
    # forward and backward of one scope: the phase goes, the scope stays
    assert table["multiply_add_fusion"] == "GPT/h/mlp"
    # two scopes that agree on nothing: the fusion's own op_name stands,
    # as it does where one stray instruction empties the prefix of an
    # otherwise single-scope fusion (fusion.11); none here, so the scope
    # most of its instructions carry, the first of equals
    assert table["fusion.9"] == "bwd/GPT/h/ln_1"
    assert table["fusion.11"] == "optimizer"
    assert table["all-reduce-start.1"] == "optimizer"
    assert table["dwt_fa_fwd.3"] == "fwd/GPT/h/attn"
    # no name of their own: made for whoever reads them
    assert table["copy.5"] == "GPT/h/mlp"
    assert table["Arg_1.2"] == "fwd/GPT/h/mlp/c_fc"
    # instructions inside a fused computation run as their fusion
    assert "convolution.1" not in table and "exp.1" not in table


_X = "jit(train_step)/jvp(X)/layers_0"
_X_BWD = "jit(train_step)/transpose(jvp(X))"
_OWNERS_HLO = f"""\
HloModule jit_train_step, entry_computation_layout={{()->f32[]}}

%fused_mix (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  %negate.1 = f32[8]{{0}} negate(%p0), metadata={{op_name="{_X}/norm/neg"}}
  ROOT %multiply.1 = f32[8]{{0}} multiply(%negate.1, %negate.1), metadata={{op_name="{_X}/mlp/up/mul"}}
}}

%fused_remat (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  %exp.2 = f32[8]{{0}} exponential(%p0), metadata={{op_name="{_X_BWD}/jvp(X)/checkpoint/rematted_computation/layers_1/mlp/up/exp"}}
  ROOT %multiply.2 = f32[8]{{0}} multiply(%exp.2, %p0), metadata={{op_name="{_X_BWD}/layers_1/mlp/up/mul"}}
}}

%fused_moves (p0: f32[2,4]) -> f32[4,2] {{
  %p0 = f32[2,4]{{1,0}} parameter(0)
  %copy.3 = f32[2,4]{{0,1}} copy(%p0), metadata={{op_name="{_X}/attn/transpose"}}
  %bitcast.3 = f32[2,4]{{0,1}} bitcast(%copy.3)
  ROOT %transpose.3 = f32[4,2]{{1,0}} transpose(%bitcast.3), dimensions={{1,0}}, metadata={{op_name="{_X}/attn/transpose"}}
}}

%fused_moves_and_adds (p0: f32[2,4]) -> f32[4,2] {{
  %p0 = f32[2,4]{{1,0}} parameter(0)
  %add.4 = f32[2,4]{{1,0}} add(%p0, %p0), metadata={{op_name="{_X}/attn/add"}}
  ROOT %transpose.4 = f32[4,2]{{1,0}} transpose(%add.4), dimensions={{1,0}}, metadata={{op_name="{_X}/attn/transpose"}}
}}

%fused_root_only (p0: f32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %add.5 = f32[8]{{0}} add(%p0, %p0), metadata={{op_name="jit(train_step)/jvp(X)/add"}}
}}

%async_computation.1 (p0: f32[8]) -> f32[4] {{
  %p0 = f32[8]{{0}} parameter(0)
  ROOT %slice.6 = f32[4]{{0:S(1)}} slice(%p0), slice={{[0:4]}}
}}

ENTRY %main.1 (Arg_0.1: f32[8], Arg_1.2: f32[2,4]) -> (f32[8], f32[8]) {{
  %Arg_0.1 = f32[8]{{0}} parameter(0)
  %Arg_1.2 = f32[2,4]{{1,0}} parameter(1)
  %mix = f32[8]{{0}} fusion(%Arg_0.1), kind=kLoop, calls=%fused_mix, metadata={{op_name="{_X}/mlp/up/mul"}}
  %copy.10 = f32[8]{{0:T(256)}} copy(%mix)
  %copy.11 = f32[8]{{0:T(512)}} copy(%mix)
  %reshape.12 = f32[2,4]{{1,0}} reshape(%mix)
  %transpose.13 = f32[4,2]{{1,0}} transpose(%reshape.12), dimensions={{1,0}}
  %copy.14 = f32[4,2]{{0,1}} copy(%transpose.13)
  %kernel.1 = f32[8]{{0}} custom-call(%copy.10, %copy.11, %copy.14), custom_call_target="tpu_custom_call", metadata={{op_name="{_X_BWD}/layers_0/attn/pallas_call"}}
  %update.1 = f32[8]{{0}} multiply(%copy.11, %kernel.1), metadata={{op_name="jit(train_step)/optimizer/mul"}}
  %copy-start.20 = (f32[8]{{0:S(1)}}, f32[8]{{0}}, u32[]{{:S(2)}}) copy-start(%Arg_0.1)
  %copy-done.20 = f32[8]{{0:S(1)}} copy-done(%copy-start.20)
  %slice-start.21 = ((f32[8]{{0}}), f32[4]{{0:S(1)}}, s32[]{{:S(2)}}) slice-start(%Arg_0.1), slice={{[0:4]}}, metadata={{op_name="jit(train_step)/jvp(X)/wte/slice"}}
  %slice-done.21 = f32[4]{{0:S(1)}} slice-done(%slice-start.21)
  %slice-start.22 = ((f32[8]{{0}}), f32[4]{{0:S(1)}}, s32[]{{:S(2)}}) async-start(%mix), calls=%async_computation.1
  %slice-done.22 = f32[4]{{0:S(1)}} async-done(%slice-start.22)
  %kernel.2 = f32[4]{{0}} custom-call(%slice-done.22), custom_call_target="tpu_custom_call", metadata={{op_name="{_X}/attn/pallas_call"}}
  %remat = f32[8]{{0}} fusion(%copy-done.20), kind=kLoop, calls=%fused_remat
  %moves = f32[4,2]{{1,0}} fusion(%Arg_1.2), kind=kLoop, calls=%fused_moves
  %moves_and_adds = f32[4,2]{{1,0}} fusion(%Arg_1.2), kind=kLoop, calls=%fused_moves_and_adds
  %root_only = f32[8]{{0}} fusion(%remat), kind=kLoop, calls=%fused_root_only, metadata={{op_name="jit(train_step)/jvp(X)/add"}}
  %joined = f32[8]{{0}} add(%root_only, %root_only), metadata={{op_name="{_X}/mlp/up/add;{_X}/mlp/down/add"}}
  %copy.30 = f32[8]{{0}} copy(%Arg_0.1)
  ROOT %tuple.1 = (f32[8]{{0}}, f32[8]{{0}}) tuple(%update.1, %copy.30)
}}
"""


@pytest.fixture(scope="module")
def owned():
    return owners(_OWNERS_HLO)


def test_a_copy_between_two_scopes_is_made_for_its_reader(owned):
    # producer fwd/X/layers/mlp/up, reader the backward attention kernel
    assert owned["copy.10"] == {"scope": "bwd/X/layers/attn",
                                "via": "consumer", "kind": "move",
                                "members": []}


def test_readers_that_disagree_leave_a_copy_to_its_producer(owned):
    # read by the kernel and by the optimizer's update
    assert owned["copy.11"]["scope"] == "fwd/X/layers/mlp/up"
    assert owned["copy.11"]["via"] == "producer"


@pytest.mark.parametrize("start,done,scope,via", [
    # no name on either half: the pair is made for the fusion that reads it
    ("copy-start.20", "copy-done.20", "X/layers/mlp/up", "consumer"),
    # the name is on the start alone: the done takes it
    ("slice-start.21", "slice-done.21", "fwd/X/wte", "own"),
    # the chip's text: `async-start` calls the computation it runs, and
    # moves data where that holds nothing but a slice
    ("slice-start.22", "slice-done.22", "fwd/X/layers/attn", "consumer"),
])
def test_the_halves_of_an_async_pair_share_an_owner(owned, start, done,
                                                    scope, via):
    for half in (start, done):
        assert owned[half]["scope"] == scope, half
        assert owned[half]["via"] == via, half
        assert owned[half]["kind"] == "move", half


def test_a_chain_of_nameless_ops_resolves_to_its_end_phase_included(owned):
    for name in ("reshape.12", "transpose.13", "copy.14"):
        assert owned[name]["scope"] == "bwd/X/layers/attn", name
        assert owned[name]["via"] == "consumer", name


def test_recomputed_and_backward_members_of_one_module_agree(owned):
    """`recompute/X/X/layers/mlp/up` against `bwd/X/layers/mlp/up` stopped
    at the root; with the repeat dropped the module stands, without a
    phase."""
    assert owned["remat"]["scope"] == "X/layers/mlp/up"
    assert owned["remat"]["via"] == "common"
    assert owned["remat"]["members"] == ["recompute/X/layers/mlp/up",
                                         "bwd/X/layers/mlp/up"]


def test_a_fusion_across_two_modules_is_its_roots_and_lists_both(owned):
    # the members agree on the root and the block axis: that says nothing
    assert owned["mix"]["scope"] == "fwd/X/layers/mlp/up"
    assert owned["mix"]["via"] == "root"
    assert owned["mix"]["members"] == ["fwd/X/layers/norm",
                                       "fwd/X/layers/mlp/up"]


def test_the_models_root_alone_is_no_owner(owned):
    # every member and the fusion itself say `fwd/X`: its reader decides
    assert owned["root_only"]["scope"] == "fwd/X/layers/mlp"
    assert owned["root_only"]["via"] == "consumer"
    assert owned["root_only"]["members"] == ["fwd/X"]
    assert owned["joined"] == {"scope": "fwd/X/layers/mlp", "via": "own",
                               "kind": "compute", "members": []}


@pytest.mark.parametrize("name,kind", [
    ("moves", "move"),             # copy, bitcast, transpose
    ("moves_and_adds", "compute"),  # the same with one add
    ("copy.10", "move"), ("reshape.12", "move"), ("kernel.1", "compute"),
    ("update.1", "compute"), ("copy-start.20", "move"),
])
def test_kind_tells_what_only_moves_data(owned, name, kind):
    assert owned[name]["kind"] == kind


def test_what_nothing_names_and_nothing_reads_is_left_unowned(owned):
    # a parameter copied into the step's outputs
    assert owned["copy.30"] == {"scope": "", "via": "none", "kind": "move",
                                "members": []}
    # what runs inside a called computation runs as its caller
    assert "slice.6" not in owned and "multiply.1" not in owned
    assert scope_table(_OWNERS_HLO) == {n: e["scope"]
                                        for n, e in owned.items()}


def test_relayouts_counts_the_copies_made_for_a_module():
    """An inherited scope counts as an own one: the three copies and the
    chain's reshape and transpose are the kernel's; the copy its
    producer keeps is the MLP's; an async pair stages, it re-lays
    nothing."""
    assert relayouts(_OWNERS_HLO, "attn") == {
        "copy.10": "copy", "reshape.12": "reshape",
        "transpose.13": "transpose", "copy.14": "copy", "moves": "fusion"}
    assert relayouts(_OWNERS_HLO, "mlp") == {"copy.11": "copy"}
    assert relayouts(_OWNERS_HLO, "mlp", outside=("up",)) == {}


def test_operands_are_read_past_printed_types_and_layouts():
    comps = parse_computations(_OWNERS_HLO)
    entry = {i["name"]: i for i in comps["main.1"]}
    assert entry["kernel.1"]["operands"] == ["copy.10", "copy.11", "copy.14"]
    assert entry["copy-start.20"]["shape"] == \
        "(f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)})"
    assert entry["Arg_0.1"]["operands"] == []
    typed = parse_computations(
        "ENTRY %e (a: f32[8]) -> f32[8] {\n"
        "  %a = f32[8]{0:T(8,128)(2,1)} parameter(0)\n"
        "  ROOT %s = (f32[8]{0}, f32[8]{0}) all-reduce-start("
        "f32[8]{0:T(8,128)(2,1)} %a, f32[8]{0} %a), replica_groups={}\n"
        "}\n")["e"]
    assert typed[1]["operands"] == ["a", "a"]


def test_collectives_are_read_through_the_same_line_reader():
    assert list(iter_collectives(_HAND_HLO)) == [
        ("all-reduce", [("f32", (8,)), ("f32", (8,))],
         "jit(train_step)/optimizer/reduce_sum")]
    assert list(iter_collectives(_OWNERS_HLO)) == []


def _lower(remat: bool, accum: int = 1) -> str:
    import jax
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=256, n_layer=2, n_head=4, n_embd=64,
                    block_size=32, dtype=jnp.float32, remat=remat)
    res = auto_accelerate(GPT(cfg), strategy=[("fsdp", {})],
                          devices=list(jax.devices("cpu"))[:1],
                          materialize=False, accum_steps=accum)
    shape = (8, 32) if accum == 1 else (accum, 8, 32)
    batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32)
             for k in ("input_ids", "labels")}
    return res.train_step.lower(res.state, batch).compile().as_text()


@pytest.fixture(scope="module")
def plain_hlo():
    return _lower(remat=False)


def _part(scope: str) -> str:
    parts = scope.split("/")
    for name in ("optimizer", "head", "loss", "mlp", "attn"):
        if name in parts:
            return name
    return ""


def test_every_matmul_of_the_real_step_is_in_a_named_part(plain_hlo):
    comps = parse_computations(plain_hlo)
    matmuls = [i for body in comps.values() for i in body
               if i["opcode"] in ("dot", "convolution")]
    assert len(matmuls) >= 2 * (4 * 3 + 3) + 3  # blocks fwd+bwd, head
    parts = {}
    for ins in matmuls:
        part = _part(scope_of(ins["op_name"]))
        assert part in ("attn", "mlp", "head"), (ins["name"],
                                                 ins["op_name"])
        parts[part] = parts.get(part, 0) + 1
    # the tied head: logits, d(hidden), d(wte)
    assert parts["head"] == 3
    assert parts["mlp"] == 2 * 2 * 3  # 2 layers x (c_fc, c_proj) x 3
    table = scope_table(plain_hlo)
    top = {i["name"]: i for body in comps.values() for i in body}
    for name, scope in table.items():
        if top[name]["opcode"] in ("dot", "convolution"):
            assert _part(scope) in ("attn", "mlp", "head")


def test_loss_head_and_optimizer_carry_their_scopes(plain_hlo):
    names = set(re.findall(r'op_name="([^"]*)"', plain_hlo))
    scopes = {scope_of(n) for n in names}
    assert "fwd/loss" in scopes and "bwd/loss" in scopes
    assert any(s.startswith("fwd/GPT/head") for s in scopes)
    assert any(s.startswith("bwd/GPT/head") for s in scopes)
    assert "optimizer" in scopes
    table = scope_table(plain_hlo)
    assert sum(s == "optimizer" for s in table.values()) > 50
    # nothing of the update is left without its scope: what stays bare
    # under jit(train_step) is the step counter's increment
    bare = [n for n in names if re.fullmatch(r"jit\(train_step\)/\w+", n)]
    assert len(bare) <= 2, bare


def test_remat_and_accumulation_keep_the_parts():
    hlo = _lower(remat=True, accum=2)
    scopes = {scope_of(n)
              for n in re.findall(r'op_name="([^"]*)"', hlo)}
    assert any(s.startswith("recompute/") and "/mlp/" in s
               for s in scopes)
    assert any(s.startswith("bwd/accum/") for s in scopes)
    assert any(s.startswith("fwd/accum/") and s.endswith("/loss")
               for s in scopes)
    comps = parse_computations(hlo)
    for ins in (i for body in comps.values() for i in body
                if i["opcode"] in ("dot", "convolution")):
        assert _part(scope_of(ins["op_name"])) in ("attn", "mlp", "head")


def test_instructions_of_lists_one_opcode_under_a_scope_with_its_shape():
    # inside fused computations as well as at module level
    assert instructions_of(_HAND_HLO, "multiply", "h/mlp") == {
        "multiply.1": "f32[8]{0}"}
    assert instructions_of(_HAND_HLO, "multiply", "optimizer") == {
        "multiply.8": "f32[8]{0}"}
    assert set(instructions_of(_HAND_HLO, "multiply", "")) == {
        "multiply.1", "multiply.8"}
    assert instructions_of(_HAND_HLO, "fusion", "h/mlp") == {}
    assert instructions_of(_HAND_HLO, "custom-call", "h/attn") == {
        "dwt_fa_fwd.3": "bf16[8,256]{1,0}"}
    # a path matches whole components: `h/ml` is not `h/mlp`
    assert instructions_of(_HAND_HLO, "multiply", "h/ml") == {}


@pytest.mark.parametrize("held,num_experts", [(8, 8), (2, 8)])
def test_the_dropless_layer_scatters_no_row_forward_or_backward(
        held, num_experts):
    """`instructions_of` on a real MoE layer's forward + backward,
    compiled here: under `moe/dispatch` and `moe/combine` the only
    scatter left counts group sizes (integers), and the rows move by
    four gathers — two each way, the backward passes' under the scope of
    the call they are the backward of."""
    import jax
    import jax.numpy as jnp

    from dlrover_wuqiong_tpu.models.moe import MoEConfig, MoEMLP

    cfg = MoEConfig(num_experts=num_experts, top_k=2, impl="grouped",
                    dtype=jnp.float32, expert_act="relu2", aux_loss="none",
                    experts_held=held if held < num_experts else 0)
    layer = MoEMLP(hidden=48, ffn=24, moe=cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 48))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    text = jax.jit(jax.grad(
        lambda p, x: jnp.sum(jnp.sin(layer.apply({"params": p}, x))),
        argnums=(0, 1))).lower(params, x).compile().as_text()
    rows = re.compile(r"f32\[[\d,]+,48\]")  # T*k rows of the width, 48
    for scope in ("moe/dispatch", "moe/combine"):
        # what is scattered: group sizes, the top-k's gates into (T, E)
        scattered = instructions_of(text, "scatter", scope).values()
        assert not [s for s in scattered if re.match(r"f32\[\d+,48\]", s)]
        gathered = [s for s in instructions_of(text, "gather", scope).values()
                    if rows.match(s)]
        assert len(gathered) == 2, (scope, gathered)
    everywhere = [s for s in instructions_of(text, "gather", "").values()
                  if rows.match(s)]
    assert len(everywhere) == 4


@pytest.mark.parametrize("shape,nbytes", [
    ("bf16[1,4,8192,3584]{3,2,1,0:T(8,128)(2,1)}", 4 * 8192 * 3584 * 2),
    ("f32[1,32,8192]{2,1,0:T(8,128)S(1)}", 32 * 8192 * 4),
    ("(bf16[8,64]{1,0}, f32[4,32,16]{2,1,0:T(8,128)}, s32[])",
     8 * 64 * 2 + 4 * 32 * 16 * 4 + 4),
    ("pred[16,128]{1,0}", 16 * 128), ("f8e4m3fn[32,128]", 32 * 128),
    ("token[]", 0), ("", 0),
])
def test_shape_bytes_reads_arrays_and_tuples_past_their_layouts(shape,
                                                                nbytes):
    assert shape_bytes(shape) == nbytes


_M = "jit(train_step)/jvp(X)/layers_0/hc"
_LEDGER_HLO = f"""\
HloModule ledger

%one_lane (p0: bf16[4,64,128], p1: f32[64]) -> bf16[64,128] {{
  %p0 = bf16[4,64,128]{{2,1,0}} parameter(0)
  %p1 = f32[64]{{0}} parameter(1)
  %lane = bf16[1,64,128]{{2,1,0}} slice(%p0), slice={{[2:3], [0:64], [0:128]}}
  ROOT %scaled = bf16[64,128]{{1,0}} multiply(%lane, %p1), metadata={{op_name="{_M}/pre/mul"}}
}}

%all_lanes (q0: bf16[4,64,128]) -> bf16[64,128] {{
  %q0 = bf16[4,64,128]{{2,1,0}} parameter(0)
  %first = bf16[1,64,128]{{2,1,0}} slice(%q0), slice={{[0:1], [0:64], [0:128]}}
  ROOT %summed = bf16[64,128]{{1,0}} reduce(%q0, %first), metadata={{op_name="{_M}/read_out/reduce_sum"}}
}}

%round (c: (f32[4,64])) -> (f32[4,64]) {{
  %c = (f32[4,64]{{1,0}}) parameter(0)
  %m = f32[4,64]{{1,0}} get-tuple-element(%c), index=0
  %n = f32[4,64]{{1,0}} divide(%m, %m), metadata={{op_name="{_M}/sinkhorn/while/body/div"}}
  ROOT %t = (f32[4,64]{{1,0}}) tuple(%n)
}}

ENTRY %main (x: bf16[4,64,128], w: f32[64], a: f32[4,64]) -> bf16[64,128] {{
  %x = bf16[4,64,128]{{2,1,0}} parameter(0)
  %w = f32[64]{{0}} parameter(1)
  %a = f32[4,64]{{1,0}} parameter(2)
  %fusion.1 = bf16[64,128]{{1,0}} fusion(%x, %w), kind=kLoop, calls=%one_lane, metadata={{op_name="{_M}/pre/mul"}}
  %fusion.2 = bf16[64,128]{{1,0}} fusion(%x), kind=kLoop, calls=%all_lanes, metadata={{op_name="{_M}/read_out/reduce_sum"}}
  %copy-start.1 = (bf16[64,128]{{1,0}}, bf16[64,128]{{1,0}}, u32[]) copy-start(%fusion.1), metadata={{op_name="{_M}/pre/mul"}}
  %copy-done.1 = bf16[64,128]{{1,0:S(1)}} copy-done(%copy-start.1), metadata={{op_name="{_M}/pre/mul"}}
  %init = (f32[4,64]{{1,0}}) tuple(%a)
  %while.1 = (f32[4,64]{{1,0}}) while(%init), condition=%cond, body=%round, metadata={{op_name="{_M}/sinkhorn/while"}}
  %dwt_hc_post.1 = bf16[4,64,128]{{2,1,0}} custom-call(%a, %x, %copy-done.1), custom_call_target="tpu_custom_call", metadata={{op_name="{_M}/post_res/dwt_hc_post/pallas_call"}}
  %elsewhere = bf16[64,128]{{1,0}} add(%fusion.2, %fusion.2), metadata={{op_name="jit(train_step)/jvp(X)/layers_0/norm/add"}}
  ROOT %out = bf16[64,128]{{1,0}} add(%elsewhere, %copy-done.1), metadata={{op_name="jit(train_step)/jvp(X)/head/add"}}
}}
"""


def test_moved_bytes_is_a_scopes_byte_ledger():
    """Operands and result of every device op under a scope: a fusion's
    operand it only slices at the slice, one it reads whole (beside a
    slice) whole; an async pair once, at what arrives; a loop's body
    once and the `while` itself nothing; a kernel its operands and its
    result; nothing of another scope, no parameter, no tuple."""
    lane, stream = 64 * 128 * 2, 4 * 64 * 128 * 2
    moved = moved_bytes(_LEDGER_HLO, "hc")
    assert {name: (e["read"], e["written"]) for name, e in moved.items()} \
        == {"fusion.1": (lane + 64 * 4, lane), "fusion.2": (stream, lane),
            "copy-done.1": (lane, lane), "n": (2 * 4 * 64 * 4, 4 * 64 * 4),
            "dwt_hc_post.1": (4 * 64 * 4 + stream + lane, stream)}
    assert moved["dwt_hc_post.1"]["scope"] \
        == "fwd/X/layers/hc/post_res/dwt_hc_post"
    assert set(moved_bytes(_LEDGER_HLO, "hc/pre")) == {"fusion.1",
                                                      "copy-done.1"}
    assert set(moved_bytes(_LEDGER_HLO, "norm")) == {"elsewhere"}
