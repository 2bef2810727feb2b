"""The LFM2-24B-A2B configuration: published widths and the five cuts,
what `build` refuses, operation counts against hand arithmetic, the
readers on its scopes file and on the program's counters, its plain
reference against the program at a tiny size on the CPU (both float32),
and the cell's control flow rehearsed on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, program, worker
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

CELL = "lfm2_24b_a2b.steady"
JOINED = ("step.moe_experts_ms", "step.moe_route_ms",
          "kernel.moe_gmm_roofline", "moe.load_max_over_mean",
          "moe.held_rows_share")
NEW = ("step.shortconv_ms", "step.shortconv_gated_ms", "shortconv.roofline",
       "shortconv.plain_calls_share")
REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers",
           "num_experts", "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "lfm2_moe")


def _catalog_row() -> dict:
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "LFM2-24B-A2B" in line]
    return next(r for r in rows if r["name"] == "LFM2-24B-A2B")


def test_widths_are_the_catalog_rows_and_five_cuts_are_listed(cell, mod):
    cfg, row = cell["config"], _catalog_row()
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, published in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == published, key  # rope_parameters whole too
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"]) == (5, 1, 8)
    assert cfg["layer_types"] == row["config"]["layer_types"][1:6] == \
        ["conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    share = cfg["share"]
    assert (share["num_experts_published"], share["first_expert"],
            share["ep"], share["vocabulary_slices"],
            share["pipeline_stages"], share["stage"],
            share["parameters"]) == (64, 0, 8, 8, 8, 1, 469_285_248)
    for key in ("tied_table", "conv_mixer", "conv_precision", "qk_norm",
                "rope", "router", "selection_bias", "expert_form", "norms",
                "auxiliary_loss", "initializer", "unused_keys"):
        assert cfg["assumed"][key], key
    for key in ("tied_table", "conv_mixer", "qk_norm", "router"):
        assert "NOT TAKEN" in cfg["assumed"][key], key
    assert "first of eight" in cfg["deployment"]
    assert cfg["num_params"]["total"] == 469_285_248
    assert cfg["num_params"]["uncut"] == 23_843_661_440
    assert "469,285,248" in cfg["num_params"]["sum"]
    rung = cfg["train"]["memory_rung"]
    assert rung["limit_GB"] == 14.4 and len(rung["live_GB"]) == 5
    taken = next(k for k in rung["live_GB"] if k.startswith(rung["taken"]))
    assert rung["live_GB"][taken] < 14.4 < rung["live_GB"]["over: 5 x 8192"]
    assert taken == f"a: {cell['global_batch']} x {cell['seq_len']}"
    assert (cell["chips"], cell["global_batch"], cell["seq_len"],
            cell["traffic_name"]) == (1, 4, 8192, "steady")
    c = mod.build(cfg).config
    assert (c.hidden_size, c.dense_width, c.conv_taps,
            c.num_heads, c.num_kv_heads, c.num_experts, c.experts_held,
            c.first_expert, c.top_k, c.expert_width, c.routed_scaling,
            c.gate_norm_eps, c.vocab_size, c.num_layers, c.num_dense_layers,
            c.rope_theta, c.rms_eps, c.max_seq_len) == \
        (2048, 11776, 3, 32, 8, 64, 8, 0, 4, 1536, 1.0, 1e-6, 8192,
         5, 1, 1e6, 1e-5, 128000)
    assert c.attention_config().qk_head_norm
    assert not c.attention_config().qk_norm
    assert c.moe_config().selection_bias and not c.moe_config().shared_width
    assert (c.remat, c.remat_policy, c.bias_update_rate) == \
        (True, "full", 0.05)
    assert c.num_params() == 469_285_248


@pytest.mark.parametrize("key,value", [
    ("model_type", "lfm2"), ("use_expert_bias", False),
    ("norm_topk_prob", False), ("conv_bias", True),
    ("rope_parameters", {"rope_theta": 1000000, "rope_type": "yarn"}),
    ("num_hidden_layers", 6), ("num_dense_layers", 0),
    ("num_key_value_heads", 5), ("max_position_embeddings", 4096),
    ("layer_types", ["conv", "mamba", "conv", "conv", "conv"])])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, key, value):
    cfg = dict(cell["config"], **{key: value})
    with pytest.raises(ValueError):
        model = mod.build(cfg)
        if key == "layer_types":  # the stack's own refusal
            jax.eval_shape(model.init_params, jax.random.PRNGKey(0))


def test_operation_counts_against_hand_arithmetic(cell, mod):
    cfg, seq = cell["config"], cell["seq_len"]
    conv = 4 * 4 * 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    pairs = 2 * (64 + 64) * 32 * (seq + 1) / 2
    dense = 3 * 2048 * 11776
    sparse = 4 * (2048 * 64 + 0.5 * 3 * 2048 * 1536)
    head = 2048 * 8192
    parts = mod.dense_params_per_token(cfg)
    assert parts["conv"] == conv == 67_108_864
    assert parts["routed"] == 4 * 0.5 * 9_437_184
    assert mod.train_flops_per_token(cfg) == pytest.approx(
        6 * (conv + attn + dense + sparse + head) + 3 * pairs, rel=1e-12)
    att = mod.attention_cost_per_step(cfg, 4)
    assert att["flops"] == 6 * 2 * 64 * (seq * (seq + 1) // 2) * 4 * 32
    # q, o, dq, do once a query head; k, v and theirs once a kv head
    assert att["bytes"] == (6 * 32 + 6 * 8) * 4 * seq * 64 * 2
    moe = mod.moe_cost_per_step(cfg, 4)
    assert moe["flops"] == 4 * 9 * 2 * (4 * seq // 2) * 2048 * 1536
    sc = mod.shortconv_cost_per_step(cfg, 4)
    tokens = 4 * seq
    assert sc["flops_fwd"] == 4 * 2 * tokens * (2048 * 6144 + 2048 * 2048)
    assert sc["flops"] == 4 * sc["flops_fwd"]  # fwd, recomputed, 2 x bwd
    assert sc["bytes_fwd"] == 4 * 2 * (2048 * 6144 + 3 * 2048 + 2048 * 2048
                                       + 2 * tokens * 2048)
    assert sc["bytes"] == 4 * sc["bytes_fwd"]
    no_remat = dict(cfg, program=dict(cfg["program"], remat=False))
    assert mod.shortconv_cost_per_step(no_remat, 4)["flops"] \
        == 3 * sc["flops_fwd"]
    # from shapes alone: twice the batch is twice the products
    assert mod.shortconv_cost_per_step(cfg, 8)["flops"] == 2 * sc["flops"]
    assert sc["flops"] / 197e12 > 10 * sc["bytes"] / 819e9  # compute bound


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    assert bench["workloads"][-1]["name"] == CELL  # appended, at the end
    assert bench["configs"][-1]["name"] == cell["config_name"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(JOINED + NEW)
    for m in bench["per_layer"]:
        if m["name"] in JOINED:
            assert m["workloads"][-1] == CELL
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == list(NEW)
    assert bench["per_layer"][-4:] == new
    assert {m["layer"] for m in new} == {"short-convolution layer"}
    for w in bench["workloads"] + bench["configs"]:
        assert len(w["why"]) <= 200, w["name"]
    for m in cell["per_layer"]:  # every reader the cell asks for loads
        reader = cells.load_module("layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.SOURCE, reader.LAYER,
                reader.MOVES) == (m["name"], m["unit"], m["source"],
                                  m["layer"], m["moves"])
        assert reader.read(None, [], {}, cell) is None


def test_the_readers_split_the_step_by_the_scopes_file(monkeypatch, cell,
                                                       mod):
    rules = program.part_rules("lfm2_moe")
    assert list(rules) == ["optimizer", "head_loss", "shortconv", "mlp",
                           "attn_dense"]
    top = "Lfm2/layers"
    sc, at = f"{top}/short_conv", f"{top}/attention"
    scopes = {f"fwd/{sc}/in_proj": 30, f"recompute/{sc}/in_proj": 31,
              f"bwd/{sc}/in_proj": 60, f"fwd/{sc}/gated": 5,
              f"bwd/{sc}/gated": 9, f"fwd/{sc}/out_proj": 10,
              f"bwd/{sc}/out_proj": 20, f"fwd/{at}/q_proj": 13,
              f"fwd/{at}/o_proj": 17, f"fwd/{at}/qk_norm/q_norm": 8,
              f"fwd/{at}/rope": 4,
              f"fwd/{top}/feed_forward/moe/experts": 19,
              f"fwd/{top}/feed_forward/moe/dispatch": 23,
              f"fwd/{top}/feed_forward/gate_proj": 29,
              f"fwd/{top}/operator_norm": 3, "fwd/Lfm2/head": 7,
              "bwd/loss": 37, "optimizer": 41}
    table = {f"fusion.{i}": s for i, s in enumerate(scopes)}
    ops, t = [], 0
    for i, ms in enumerate(scopes.values()):
        ops.append([f"fusion.{i}", t, ms * 1e6])
        t += ms * 1e6
    trace = {"devices": {"0": {"modules": [["jit_train_step(1)", 0, t]],
                               "ops": ops}}, "host": []}
    monkeypatch.setattr(program, "_table", table)
    ledgers = {0: {"device": {"kind": "TPU v5 lite"}}}

    def read(name):
        return cells.load_module("layer_metrics", name).read(
            trace, [], ledgers, cell)

    whole = 30 + 31 + 60 + 5 + 9 + 10 + 20
    assert read("step.shortconv_ms") == whole
    assert read("step.shortconv_gated_ms") == 5 + 9
    assert read("step.attn_dense_ms") == 13 + 17
    assert read("step.mlp_ms") == 19 + 23 + 29
    assert read("step.moe_experts_ms") == 19
    assert read("step.moe_route_ms") == 23
    assert read("step.head_loss_ms") == 7 + 37
    assert read("step.unscoped_ms") == 8 + 4 + 3
    share = read("shortconv.roofline")
    cost = mod.shortconv_cost_per_step(cell["config"], cell["global_batch"])
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert share == pytest.approx(100 * least * 1e3 / whole)
    assert 0 < share < 100
    # a step without the mixer's scopes (another program): nothing
    monkeypatch.setattr(program, "_table", {
        name: s.replace("short_conv", "mamba") for name, s in table.items()})
    for name in ("step.shortconv_ms", "step.shortconv_gated_ms",
                 "shortconv.roofline"):
        assert read(name) is None, name


def test_the_counter_reader_reads_the_programs_counters(monkeypatch, cell):
    events = [{"ev": "open", "t": 1.0, "t_sync": 1.0, "gen": 0, "step": 10},
              {"ev": "close", "t": 9.0, "t_sync": 9.0, "gen": 0, "step": 20}]
    spans = [{"name": "trainer:step_metrics", "t_mono": t, "dur_s": 0.0,
              "attrs": {"step": 10, "shortconv_plain_calls": plain,
                        "shortconv_calls": 4.0}}
             for t, plain in ((0.5, 0.0), (2.0, 4.0), (5.0, 2.0))]
    monkeypatch.setattr(program, "setup_spans", lambda: spans)
    reader = cells.load_module("layer_metrics",
                               "shortconv.plain_calls_share")
    assert reader.read(None, events, {}, cell) == pytest.approx(75.0)
    # a program without the counters (the parent commit): nothing, no raise
    for s in spans:
        del s["attrs"]["shortconv_calls"]
    assert reader.read(None, events, {}, cell) is None


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=96,
               moe_intermediate_size=32, num_experts=4,
               num_experts_per_tok=3, max_position_embeddings=64)
    cfg["share"] = dict(cfg["share"], num_experts_published=16,
                        first_expert=4)
    cfg["train"] = dict(cfg["train"], seq_len=64)
    cfg["program"] = dict(cfg["program"], dtype="float32",
                          use_flash_attention=False)
    return cfg


def test_reference_matches_program_at_nano_f32(cell, mod):
    cfg = _nano(cell["config"])
    model = mod.build(cfg)
    # every leaf off its draw: at normal(0.02) the experts hardly reach
    # the loss, and the bias hardly weighs a gate
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape),
        model.init_params(jax.random.PRNGKey(3)))
    batch = {k: jnp.asarray(v) for k, v in
             make_data(256, 4, 64, seed=3)(0).items()}
    from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

    sys_loss, sys_norm = loss_and_grad_norm(make_lm_loss(model.apply),
                                            params, batch)
    ref_loss, ref_norm = loss_and_grad_norm(mod.reference_loss(cfg),
                                            params, batch,
                                            precision="highest")
    assert abs(sys_loss - ref_loss) / ref_loss < 1e-5
    assert abs(sys_norm - ref_norm) / ref_norm < 1e-4
    from benchmark import reference_lfm2_moe

    for wrong in reference_lfm2_moe.WRONG:
        off, off_norm = loss_and_grad_norm(
            mod.reference_loss(cfg, wrong=wrong), params, batch,
            precision="highest")
        # each wrong equation is another number
        assert abs(off - ref_loss) / ref_loss > 3e-6 \
            or abs(off_norm - ref_norm) / ref_norm > 1e-4, wrong


def test_the_cells_control_flow_runs_on_the_cpu_at_nano_size(
        cell, monkeypatch, tmp_path):
    """The worker's own `train_process` — the seeded state with its
    balanced biases, the check against the reference through the
    Trainer's compiled step, the window — on the CPU at a toy size.
    Control flow only; no number of it means anything."""
    from benchmark.drivers import trainer_inproc

    cell = dict(cell, config=_nano(cell["config"]), seq_len=64,
                global_batch=8)
    cell["config"]["correct"].update(loss_rtol=0.05, grad_norm_rtol=0.2,
                                     loss_band=[0.0, 100.0])
    monkeypatch.setattr(worker, "require_tpu", lambda chips: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite",
        "count": len(jax.devices())})
    monkeypatch.setenv("DWT_JOB_NAME", f"bmtest{os.getpid()}")

    class Args:
        seed, seconds, trace = 2147483659, 1.0, 0

    run = trainer_inproc.run(cell, Args, str(tmp_path), 0.0)
    rec = run["gens"][0]
    assert rec["init_check"]["ok"], rec["init_check"]
    assert rec["init_check"]["loss_rel_err"] < 1e-4
    assert rec["all_finite"] and rec["stopped_at"] > 10

    def read(kind, name):
        return cells.load_module(kind, name).read(
            None, run["events"], {0: rec}, cell)

    assert read("end_to_end", "tokens_per_s") > 0
    assert read("layer_metrics", "shortconv.plain_calls_share") == 100.0
    assert 0.0 < read("layer_metrics", "moe.held_rows_share") < 100.0
    assert read("layer_metrics", "moe.load_max_over_mean") >= 1.0
