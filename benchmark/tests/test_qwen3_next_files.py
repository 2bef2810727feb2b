"""The Qwen3-Next-80B-A3B configuration: published widths and the three
cuts, what `build` refuses, operation counts against hand arithmetic, the
readers on its scopes file and on the program's counters, its plain
reference against the program at a tiny size on the CPU (both float32),
the seeded state's drawn norms, and the cell's control flow rehearsed on
the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, program, worker
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

CELL = "qwen3_next_80b_a3b.steady"
JOINED = ("step.linattn_ms", "step.linattn_scan_ms", "kernel.delta_roofline",
          "linattn.padded_lanes_share", "step.attn_gate_ms",
          "attn.gate_mean", "attn.padded_lanes_share",
          "step.moe_experts_ms", "step.moe_route_ms",
          "kernel.moe_gmm_roofline", "moe.load_max_over_mean",
          "moe.held_rows_share")
NEW = ("linattn.qk_repeat_share", "step.moe_shared_ms",
       "moe.shared_gate_mean")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMS = 424_340_544


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "qwen3_next")


def _catalog_row() -> dict:
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "Qwen3-Next" in line]
    return next(r for r in rows
                if r["name"] == "Qwen3-Next-80B-A3B-Instruct")


def test_widths_are_the_catalog_rows_and_three_cuts_are_listed(cell, mod):
    cfg, row = cell["config"], _catalog_row()
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, published in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == published, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"]) == (4, 16)
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    share = cfg["share"]
    assert (share["num_experts_published"], share["first_expert"],
            share["ep"], share["vocabulary_slices"],
            share["num_hidden_layers_published"], share["pipeline_stages"],
            share["stage"], share["parameters"]) == \
        (512, 0, 32, 8, 48, 12, 1, PARAMS)
    taken = ("norms", "value_head_grouping", "write_gate",
             "gates_per_value_head", "l2_norm_eps", "convolution",
             "attention_gate", "qk_norm", "rotation", "no_bias",
             "shared_expert", "router", "auxiliary_loss")
    for key in taken + ("mtp", "initializer", "unused_keys"):
        assert cfg["assumed"][key], key
    for key in taken:
        assert "NOT TAKEN" in cfg["assumed"][key], key
    assert "seeded_leaves" in cfg["assumed"]["initializer"]
    assert "first of twelve" in cfg["deployment"]
    assert cfg["num_params"]["total"] == PARAMS
    assert cfg["num_params"]["uncut"] == 79_674_391_296
    assert cfg["num_params"]["issue_32_held"] == 625_667_136
    assert "424,340,544" in cfg["num_params"]["sum"]
    rung = cfg["train"]["memory_rung"]
    assert rung["limit_GB"] == 14.4 and len(rung["live_GB"]) == 4
    by_letter = {k[0]: v for k, v in rung["live_GB"].items()}
    # the issue's order: the first that passes is the one taken
    assert by_letter["a"] > 14.4 and by_letter["b"] > 14.4 \
        and by_letter["c"] < 14.4 and rung["taken"] == "c"
    assert (cell["chips"], cell["global_batch"], cell["seq_len"],
            cell["traffic_name"]) == (1, 1, 16384, "steady")
    c = mod.build(cfg).config
    assert (c.hidden_size, c.num_layers, c.full_attention_interval,
            c.num_heads, c.num_kv_heads, c.head_dim, c.rotary_dim,
            c.rope_theta, c.linear_key_heads, c.linear_value_heads,
            c.linear_key_dim, c.linear_value_dim, c.conv_kernel,
            c.num_experts, c.experts_held, c.first_expert, c.top_k,
            c.expert_width, c.shared_width, c.vocab_size, c.rms_eps,
            c.max_seq_len, c.chunk_size) == \
        (2048, 4, 4, 16, 2, 256, 64, 1e7, 16, 32, 128, 128, 4, 512, 16, 0,
         10, 512, 512, 18992, 1e-6, 262144, 64)
    assert c.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    llama, lin, moe = (c.attention_config(), c.linear_config(),
                       c.moe_config())
    assert llama.qk_head_norm and llama.norm_zero_centred \
        and llama.attn_out_gate and not llama.attn_gate
    assert (lin.num_heads, lin.key_heads, lin.neg_eigval) == (32, 16, False)
    assert moe.shared_gate and moe.shared_width == 512 \
        and not moe.selection_bias and moe.norm_topk_prob
    # the balance term the file assumes (and says why), a layer's fourth
    assert c.router_aux_loss_weight == 0.01 \
        == cfg["train"]["router_aux_loss_coef"]
    assert moe.aux_loss == "topk"
    assert moe.aux_loss_weight == pytest.approx(0.01 / 4)
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == PARAMS


@pytest.mark.parametrize("key,value", [
    ("model_type", "qwen3_moe"), ("norm_topk_prob", False),
    ("use_sliding_window", True), ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0]), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"), ("rope_scaling", {"rope_type": "yarn"}),
    ("num_key_value_heads", 5), ("linear_num_key_heads", 5),
    ("partial_rotary_factor", 0.3), ("max_position_embeddings", 4096)])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, key, value):
    with pytest.raises(ValueError):
        mod.build(dict(cell["config"], **{key: value}))


def test_operation_counts_against_hand_arithmetic(cell, mod):
    cfg, seq = cell["config"], cell["seq_len"]
    causal = seq * (seq + 1) // 2
    linear = 2048 * (2 * 2048 + 2 * 4096 + 2 * 32) + 4096 * 2048
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512
    router, shared = 2048 * 512, 3 * 2048 * 512 + 2048
    routed, head = 0.3125 * 3 * 2048 * 512, 2048 * 18992
    parts = mod.dense_params_per_token(cfg)
    assert parts == {"linear": 3 * linear, "attention": attn,
                     "router": 4 * router, "shared": 4 * shared,
                     "routed": 4 * routed, "head": head}
    # the mixer's and the attention's matmul parameters are the model's
    assert linear + 4 * 8192 + 2 * 32 + 128 == 33_718_464
    assert attn + 512 == 27_263_488
    pairs = 2 * (256 + 256) * 16 * causal / seq
    recurrence = 7 * 128 * 128 * 32
    assert mod.train_flops_per_token(cfg) == pytest.approx(
        6 * sum(parts.values()) + 3 * pairs + 3 * 3 * recurrence, rel=1e-12)
    att = mod.attention_cost_per_step(cfg, 1)
    assert att["flops"] == 6 * 2 * 256 * causal * 16
    # q, o, dq, do once a query head; k, v and theirs once a kv head
    assert att["bytes"] == (6 * 16 + 6 * 2) * seq * 256 * 2
    delta = mod.delta_cost_per_step(cfg, 1)
    assert delta["flops"] == 3 * 3 * seq * recurrence
    # counted at the MODEL's 16 key heads and 32 value heads
    assert delta["bytes"] == 3 * 2 * seq * (
        2 * 16 * 128 + 2 * 32 * 128 + 2 * 32) * 2
    assert delta["bytes"] / 819e9 > delta["flops"] / 197e12  # memory bound
    assert mod.delta_cost_per_step(cfg, 2)["flops"] == 2 * delta["flops"]
    moe = mod.moe_cost_per_step(cfg, 1)
    assert moe["flops"] == 4 * 9 * 2 * (seq * 10 * 16 // 512) * 2048 * 512


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
    assert bench["per_layer"][-3:] == new
    assert {m["layer"] for m in new} == {"linear-attention layer",
                                         "expert layer"}
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
    rules = program.part_rules("qwen3_next")
    assert list(rules) == ["optimizer", "head_loss", "linattn", "mlp",
                           "attn_dense"]
    top = "Qwen3Next/layers"
    lin, at = f"{top}/linear_attention", f"{top}/attention"
    ff = f"{top}/feed_forward/moe"
    scopes = {f"fwd/{lin}/q_proj": 5, f"fwd/{lin}/gates/a_proj": 2,
              f"fwd/{lin}/conv/dwt_conv_fwd": 6,
              f"bwd/{lin}/conv/dwt_conv_bwd": 9,
              f"fwd/{lin}/delta/dwt_gdr_fwd": 40,
              f"bwd/{lin}/delta/dwt_gdr_bwd": 70,
              f"fwd/{lin}/delta/broadcast": 4,
              f"fwd/{lin}/gate_norm": 3, f"fwd/{lin}/o_proj": 8,
              f"fwd/{at}/q_proj": 13, f"fwd/{at}/o_proj": 17,
              f"fwd/{at}/qk_norm/q_norm": 8, f"fwd/{at}/rope_partial": 4,
              f"fwd/{at}/gate": 6, f"bwd/{at}/gate": 7,
              f"fwd/{ff}/experts": 19, f"fwd/{ff}/dispatch": 23,
              f"fwd/{ff}/shared/shared_up_proj": 11,
              f"bwd/{ff}/shared/shared_expert_gate": 1,
              f"fwd/{top}/input_norm": 3, "fwd/Qwen3Next/head": 7,
              "bwd/loss": 37, "optimizer": 43}
    table = {f"fusion.{i}": s for i, s in enumerate(scopes)}
    ops, t = [], 0
    for i, ms in enumerate(scopes.values()):
        ops.append([f"fusion.{i}", t, ms * 1e6])
        t += ms * 1e6
    # the attention's own kernel: kernel.attn_ms's, in no part
    ops.append(["dwt_fa_fwd.1", t, 100e6])
    trace = {"devices": {"0": {"modules": [["jit_train_step(1)", 0,
                                            t + 100e6]],
                               "ops": ops}}, "host": []}
    monkeypatch.setattr(program, "_table", table)
    ledgers = {0: {"device": {"kind": "TPU v5 lite"}}}

    def read(name):
        return cells.load_module("layer_metrics", name).read(
            trace, [], ledgers, cell)

    scan = 6 + 9 + 40 + 70 + 4
    assert read("step.linattn_ms") == scan + 5 + 2 + 3 + 8
    assert read("step.linattn_scan_ms") == scan
    assert read("kernel.attn_ms") == 100
    assert read("step.attn_dense_ms") == 13 + 17
    assert read("step.attn_gate_ms") == 6 + 7
    assert read("step.mlp_ms") == 19 + 23 + 11 + 1
    assert read("step.moe_experts_ms") == 19
    assert read("step.moe_route_ms") == 23 + 11 + 1
    assert read("step.moe_shared_ms") == 11 + 1
    assert read("step.head_loss_ms") == 7 + 37
    assert read("step.unscoped_ms") == 8 + 4 + 6 + 7 + 3
    share = read("kernel.delta_roofline")
    cost = mod.delta_cost_per_step(cell["config"], cell["global_batch"])
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert share == pytest.approx(100 * least * 1e3 / scan)
    assert 0 < share < 100
    main = read("kernel.attn_roofline")
    cost = mod.attention_cost_per_step(cell["config"], cell["global_batch"])
    assert main == pytest.approx(100 * max(
        cost["flops"] / 197e12, cost["bytes"] / 819e9) * 1e3 / 100)
    # a step without the scope (the parent's program): nothing, no raise
    monkeypatch.setattr(program, "_table", {
        name: s.replace("shared", "other") for name, s in table.items()})
    assert read("step.moe_shared_ms") is None
    # a class without `shared_parts` (every other cell's): nothing
    other = dict(cell, config=dict(cell["config"], model_class="lfm2_moe"))
    monkeypatch.setattr(program, "_table", table)
    assert cells.load_module("layer_metrics", "step.moe_shared_ms").read(
        trace, [], ledgers, other) is None


def test_the_counter_readers_read_the_programs_counters(monkeypatch, cell):
    events = [{"ev": "open", "t": 1.0, "t_sync": 1.0, "gen": 0, "step": 10},
              {"ev": "close", "t": 9.0, "t_sync": 9.0, "gen": 0, "step": 20}]
    spans = [{"name": "trainer:step_metrics", "t_mono": t, "dur_s": 0.0,
              "attrs": {"step": 10, "delta_qk_rows_run": 96.0,
                        "delta_qk_rows_model": 48.0,
                        "moe_shared_gate_mean": gate,
                        "attn_gate_mean": 0.5, "attn_lanes_run": 512.0,
                        "attn_lanes_model": 512.0}}
             for t, gate in ((0.5, 0.9), (2.0, 0.4), (5.0, 0.6))]
    monkeypatch.setattr(program, "setup_spans", lambda: spans)

    def read(name):
        return cells.load_module("layer_metrics", name).read(
            None, events, {}, cell)

    assert read("linattn.qk_repeat_share") == pytest.approx(50.0)
    assert read("moe.shared_gate_mean") == pytest.approx(0.5)
    assert read("attn.gate_mean") == pytest.approx(0.5)
    assert read("attn.padded_lanes_share") == 0.0
    # a program without the counters (the parent commit): nothing, no raise
    for s in spans:
        for key in ("delta_qk_rows_run", "delta_qk_rows_model",
                    "moe_shared_gate_mean"):
            del s["attrs"][key]
    for name in (NEW[0], NEW[2]):
        assert read(name) is None, name


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=4,
               linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=8, linear_value_head_dim=8,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               num_experts=4, num_experts_per_tok=3,
               max_position_embeddings=64)
    cfg["share"] = dict(cfg["share"], num_experts_published=16,
                        first_expert=4)
    cfg["train"] = dict(cfg["train"], seq_len=64)
    cfg["program"] = dict(cfg["program"], dtype="float32",
                          delta_chunk_size=16)
    return cfg


def test_the_seeded_state_draws_the_norms_and_widens_the_gates(mod, cell):
    """Every zero-centred scale off 0, the gate halves of the attention's
    `q_proj` times four, and no other leaf."""
    model = mod.build(_nano(cell["config"]))
    params = model.init_params(jax.random.PRNGKey(3))
    drawn = mod.seeded_leaves(params, jax.random.PRNGKey(4))
    moved = {jax.tree_util.keystr(p) for (p, a), b in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree.leaves(drawn)) if not bool(jnp.array_equal(a, b))}
    gated = "['layers_3']['attention']['q_proj']['kernel']"
    # two block norms a layer, the attention's two head norms, the last
    assert len(moved) == 4 * 2 + 2 + 1 + 1 and gated in moved
    assert all(name.endswith("['scale']") for name in moved - {gated})
    assert not any("gate_norm" in name for name in moved)
    w = drawn["layers_0"]["input_norm"]["scale"]
    assert 0.1 < float(jnp.std(w)) < 0.3 and abs(float(jnp.mean(w))) < 0.1
    q = drawn["layers_3"]["attention"]["q_proj"]["kernel"].reshape(
        64, 4, 2, 16)
    was = params["layers_3"]["attention"]["q_proj"]["kernel"].reshape(
        64, 4, 2, 16)
    assert bool(jnp.array_equal(q[:, :, 0], was[:, :, 0]))  # the queries
    assert bool(jnp.array_equal(q[:, :, 1], 4.0 * was[:, :, 1]))


def test_reference_matches_program_at_nano_f32(cell, mod):
    cfg = _nano(cell["config"])
    model = mod.build(cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 128))
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape),
        mod.seeded_leaves(model.init_params(jax.random.PRNGKey(3)),
                          jax.random.PRNGKey(5)))
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
    from benchmark import reference_qwen3_next

    for wrong in reference_qwen3_next.WRONG:
        off, off_norm = loss_and_grad_norm(
            mod.reference_loss(cfg, wrong=wrong), params, batch,
            precision="highest")
        assert abs(off - ref_loss) / ref_loss > 3e-6 \
            or abs(off_norm - ref_norm) / ref_norm > 1e-4, wrong


def test_the_cells_control_flow_runs_on_the_cpu_at_nano_size(
        cell, monkeypatch, tmp_path):
    """The worker's own `train_process` — the seeded state, the check
    against the reference through the Trainer's compiled step, the
    window — on the CPU at a toy size.  Control flow only; no number of
    it means anything."""
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
    assert read("layer_metrics", "linattn.qk_repeat_share") == 50.0
    assert read("layer_metrics", "linattn.padded_lanes_share") == 0.0
    assert read("layer_metrics", "attn.padded_lanes_share") == 0.0
    assert 0.0 < read("layer_metrics", "moe.shared_gate_mean") < 1.0
    assert 0.0 < read("layer_metrics", "attn.gate_mean") < 1.0
    assert 0.0 < read("layer_metrics", "moe.held_rows_share") < 100.0
    assert read("layer_metrics", "moe.load_max_over_mean") >= 1.0
