"""HLO scope table (analysis/hlo_scopes.py): op_name normalisation and
fusion resolution on hand-written HLO, then the repo's REAL
`make_train_step` lowered for a 2-layer GPT (as test_hlo_budget.py
lowers it): every matmul of the step lands in a named part, the loss is
named forward and backward, and the optimizer's ops carry its scope.
"""

import re

import pytest

from dlrover_wuqiong_tpu.analysis.hlo_scopes import (
    instructions_of,
    parse_computations,
    scope_of,
    scope_table,
)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp(GPT)/h_3/mlp/c_fc/dot_general",
     "fwd/GPT/h/mlp/c_fc"),
    ("jit(train_step)/transpose(jvp(GPT))/h_11/attn/c_attn/dot_general",
     "bwd/GPT/h/attn/c_attn"),
    ("jit(train_step)/transpose(jvp(GPT))/jvp(GPT)/checkpoint/"
     "rematted_computation/h_0/mlp/c_proj/dot_general",
     "recompute/GPT/GPT/h/mlp/c_proj"),
    # backward of a checkpointed block: `checkpoint` alone is a wrapper
    ("jit(train_step)/transpose(jvp(GPT))/jvp(GPT)/checkpoint/h_0/ln_1/"
     "mul", "bwd/GPT/GPT/h/ln_1"),
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
    # two scopes that agree on nothing: the fusion's own op_name stands
    # (none here), as it does where one stray instruction empties the
    # prefix of an otherwise single-scope fusion
    assert table["fusion.9"] == ""
    assert table["fusion.11"] == "optimizer"
    assert table["all-reduce-start.1"] == "optimizer"
    assert table["dwt_fa_fwd.3"] == "fwd/GPT/h/attn"
    assert table["copy.5"] == "" and table["Arg_1.2"] == ""
    # instructions inside a fused computation run as their fusion
    assert "convolution.1" not in table and "exp.1" not in table


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
