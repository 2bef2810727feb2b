"""The OLMoE configuration: published widths, operation counts against
hand arithmetic, the four readers it brings, and its plain reference
against the program at a tiny size on the CPU, both in float32."""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, program
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "olmoe_1b_7b.steady"
NEW = ("step.moe_experts_ms", "step.moe_route_ms", "kernel.moe_gmm_roofline",
       "moe.load_max_over_mean")
# the catalog row OLMoE-1B-7B-0125-Instruct (model-configs guide), `config`
CATALOG = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 1024,
           "max_position_embeddings": 4096, "model_type": "olmoe",
           "norm_topk_prob": False, "num_attention_heads": 16,
           "num_experts": 64, "num_experts_per_tok": 8,
           "num_hidden_layers": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "tie_word_embeddings": False, "vocab_size": 50304}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def test_widths_are_the_catalog_rows_and_only_depth_is_reduced(cell):
    cfg = cell["config"]
    assert cfg["reduced"] == ["num_hidden_layers"] == list(cfg["changed"])
    for key, published in CATALOG.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == published, key
    assert cfg["num_hidden_layers"] == 1
    assert cfg["hidden_size"] // cfg["num_attention_heads"] == 128
    assert cfg["assumed"]["router_z_loss_coef"] == 0.001
    assert cfg["router_aux_loss_coef"] == 0.01
    assert (cell["chips"], cell["seq_len"]) == (1, 4096)
    model = cells.load_module("models", "olmoe").build(cfg)
    c = model.config
    assert (c.hidden_size, c.num_heads, c.head_dim, c.intermediate_size,
            c.vocab_size, c.max_seq_len, c.num_layers) == \
        (2048, 16, 128, 1024, 50304, 4096, 1)
    assert (c.moe.num_experts, c.moe.top_k, c.moe.norm_topk_prob,
            c.moe.impl, c.moe.aux_loss, c.qk_norm) == \
        (64, 8, False, "grouped", "topk", True)
    assert c.remat == cfg["program"]["remat"]
    assert c.num_params() == 625_616_896


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("attention_bias", True), ("clip_qkv", 8.0),
    ("tie_word_embeddings", True), ("rope_scaling", {"type": "linear"})])
def test_build_refuses_what_the_program_would_not_run_as_written(cell, key,
                                                                 value):
    cfg = dict(cell["config"], **{key: value})
    with pytest.raises(ValueError):
        cells.load_module("models", "olmoe").build(cfg)


def test_operation_counts_against_hand_arithmetic(cell):
    mod = cells.load_module("models", "olmoe")
    cfg = cell["config"]
    # per token, forward + backward, 6 FLOPs a matmul parameter passed
    head = 6 * 2048 * 50304                # 618.1 MFLOP
    experts = 6 * 8 * 3 * 2048 * 1024      # 302.0
    dense = 6 * 4 * 2048 * 2048            # 100.7
    router = 6 * 2048 * 64                 # 0.8
    causal = 6 * 4096 * 2048               # 50.3
    assert mod.train_flops_per_token(cfg) == \
        head + experts + dense + router + causal == 1_071_906_816
    att = mod.attention_cost_per_step(cfg, 2)
    kept = 4096 * 4097 // 2
    assert att["flops"] == 6 * 2 * 128 * kept * 2 * 16
    assert att["bytes"] == 12 * 2 * 16 * 4096 * 128 * 2
    moe = mod.moe_cost_per_step(cfg, 2)
    rows = 2 * 4096 * 8
    assert moe["flops"] == 9 * 2 * rows * 2048 * 1024 == 2_473_901_162_496
    assert moe["flops_fwd"] * 3 == moe["flops"]
    weights = 3 * 64 * 2048 * 1024 * 2
    assert moe["bytes"] == 5 * rows * 2048 * 2 + 3 * weights
    assert moe["bytes_fwd"] + moe["bytes_bwd"] == moe["bytes"]
    # compute-bound at the published peaks: 12.6 ms against 4.6
    assert moe["flops"] / 197e12 > 2 * moe["bytes"] / 819e9


def test_new_readers_find_nothing_where_there_is_no_moe_scope(monkeypatch):
    """On a GPT-2 trace with its scope table (recorded on the chip) and
    on no trace at all: None, never an exception — the parent commit's
    side of a traced run."""
    def load(name):
        with gzip.open(os.path.join(DATA, name), "rt") as f:
            return json.load(f)

    trace = load("steady_scoped_2steps.json.gz")
    monkeypatch.setattr(program, "_table",
                        load("steady_scoped_2steps.scopes.json.gz"))
    ledgers = {0: {"device": {"kind": "TPU v5 lite"}}}
    for cell_name in ("gpt2_124m.steady", CELL):
        cell = cells.load_cell(cell_name)
        for name in NEW:
            read = cells.load_module("layer_metrics", name).read
            assert read(trace, [], ledgers, cell) is None, (cell_name, name)
            assert read(None, [], {}, cell) is None, (cell_name, name)


def test_load_reader_takes_the_window_share_of_the_step_metrics_events(
        monkeypatch):
    read = cells.load_module("layer_metrics", "moe.load_max_over_mean").read
    events = [{"ev": "open", "t": 10.0, "t_sync": 10.0, "step": 10},
              {"ev": "close", "t": 20.0, "t_sync": 20.0, "step": 30}]
    spans = [{"name": "trainer:step_metrics", "t_mono": t, "dur_s": 0.0,
              "attrs": {"step": 0, "moe_dropped": 0.0,
                        "moe_load_max_over_mean": load}}
             for t, load in ((5.0, 9.0), (12.0, 1.25), (18.0, 1.75),
                             (25.0, 9.0))]
    spans.insert(2, {"name": "trainer:step_metrics", "t_mono": 15.0,
                     "dur_s": 0.0, "attrs": {"step": 0, "other": 7.0}})
    spans.insert(0, {"name": "ckpt:save", "t_mono": 13.0, "dur_s": 1.0,
                     "attrs": {}})
    monkeypatch.setattr(program, "setup_spans", lambda: spans)
    assert read(None, events, {}, cells.load_cell(CELL)) == 1.5


def test_experts_and_route_split_the_mlp_part(monkeypatch):
    """`step.mlp_ms` is the whole expert layer on this class, and the two
    new readers split it with the scopes file's `moe_parts`: they sum to
    it, and nothing outside `moe` leaks into either."""
    rules = program.part_rules("olmoe")
    assert rules["mlp"] == [["moe"], ["ragged_dot"]]
    ff = "Llama/layers/feed_forward/moe"
    table = {"fusion.1": f"fwd/{ff}/experts", "ragged-dot-none.1":
             "ragged_dot", "fusion.2": f"bwd/{ff}/dispatch",
             "fusion.3": f"fwd/{ff}/router", "fusion.4": f"bwd/{ff}/combine",
             "fusion.5": f"bwd/{ff}", "fusion.6": "fwd/Llama/layers/"
             "attention/q_proj", "fusion.7": "optimizer",
             "fusion.8": "fwd/Llama/layers/attention/qk_norm"}
    durs = {"fusion.1": 3e6, "ragged-dot-none.1": 5e6, "fusion.2": 7e6,
            "fusion.3": 11e6, "fusion.4": 13e6, "fusion.5": 17e6,
            "fusion.6": 19e6, "fusion.7": 23e6, "fusion.8": 29e6}
    ops, t = [], 0
    for name, dur in durs.items():
        ops.append([name, t, dur])
        t += dur
    trace = {"devices": {"0": {"modules": [["jit_train_step(1)", 0, t]],
                               "ops": ops}}, "host": []}
    monkeypatch.setattr(program, "_table", table)
    cell = cells.load_cell(CELL)

    def read(name):
        return cells.load_module("layer_metrics", name).read(
            trace, [], {}, cell)

    assert read("step.moe_experts_ms") == 3.0 + 5.0
    assert read("step.moe_route_ms") == 7.0 + 11.0 + 13.0 + 17.0
    assert read("step.mlp_ms") == \
        read("step.moe_experts_ms") + read("step.moe_route_ms")
    assert read("step.attn_dense_ms") == 19.0
    assert read("step.unscoped_ms") == 29.0


def test_reference_matches_program_at_nano_f32():
    cfg = dict(cells.load_cell(CELL)["config"])
    cfg.update(vocab_size=512, hidden_size=64, intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4, num_experts=8,
               num_experts_per_tok=2, num_hidden_layers=2,
               max_position_embeddings=64)
    cfg["train"] = dict(cfg["train"], seq_len=64)
    cfg["program"] = dict(cfg["program"], dtype="float32",
                          use_flash_attention=False)
    mod = cells.load_module("models", "olmoe")
    model = mod.build(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    batch = {k: jnp.asarray(v) for k, v in
             make_data(512, 4, 64, seed=3)(0).items()}
    from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

    sys_loss, sys_norm = loss_and_grad_norm(make_lm_loss(model.apply),
                                            params, batch)
    ref_loss, ref_norm = loss_and_grad_norm(mod.reference_loss(cfg),
                                            params, batch,
                                            precision="highest")
    # float32 on both sides: only the order of sums differs
    assert abs(sys_loss - ref_loss) / ref_loss < 1e-5
    assert abs(sys_norm - ref_norm) / ref_norm < 1e-4
