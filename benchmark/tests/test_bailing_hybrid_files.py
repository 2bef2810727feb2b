"""The Ling-3.0-flash configuration: published widths and the seven cuts,
what `build` refuses, operation counts against hand arithmetic, the
readers on its scopes file, the balanced selection bias under the group
limit, its plain reference against the program at a tiny size on the CPU
(both float32), and the cell's control flow rehearsed on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, program, worker
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

CELL = "ling3_0_flash.steady"
LISTED = ("step.linattn_ms", "step.linattn_scan_ms", "kernel.delta_roofline",
          "linattn.padded_lanes_share", "step.attn_latent_ms",
          "attn.padded_lanes_share", "step.attn_gate_ms", "attn.gate_mean",
          "step.moe_experts_ms", "step.moe_route_ms",
          "kernel.moe_gmm_roofline", "moe.load_max_over_mean",
          "moe.held_rows_share", "step.linattn_decay_ms",
          "linattn.decay_floor_share", "moe.group_limit_binds_share")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "num_attention_heads", "num_key_value_heads", "vocab_size",
           "num_nextn_predict_layers"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "bailing_hybrid")


def _catalog_row() -> dict:
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "Ling-3.0-flash" in line]
    return next(r for r in rows if r["name"] == "Ling-3.0-flash")


def test_widths_are_the_catalog_rows_and_seven_cuts_are_listed(cell, mod):
    cfg, row = cell["config"], _catalog_row()
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, published in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == published, key  # the two lists whole too
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_experts"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["num_nextn_predict_layers"]) \
        == (7, 1, 8, 16, 16, 0)
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    share = cfg["share"]
    assert (share["num_experts_published"], share["first_expert"],
            share["ep"], share["heads_published"],
            share["chips_sharing_a_mixer"], share["vocabulary_slices"],
            share["pipeline_stages"], share["stage"],
            share["parameters"]) == (512, 0, 64, 32, 2, 8, 7, 1,
                                     648_853_344)
    for key in ("layer_kinds", "safe_gate", "decay_projection", "beta",
                "output_gate", "output_norm", "qk_norm", "convolution",
                "rope", "router", "selection_bias", "seq_aux",
                "swiglu_limits", "initializer", "delta_chunk_size",
                "unused_keys"):
        assert cfg["assumed"][key], key
    for key in ("layer_kinds", "safe_gate", "beta", "output_gate",
                "qk_norm"):
        assert "NOT TAKEN" in cfg["assumed"][key], key
    assert "first of seven" in cfg["deployment"]
    assert cfg["num_params"]["total"] == 648_853_344
    assert cfg["num_params"]["uncut"] == 124_050_077_152
    assert "648,853,344" in cfg["num_params"]["sum"]
    rung = cfg["train"]["memory_rung"]
    assert rung["limit_GB"] == 14.4 and len(rung["live_GB"]) == 2
    assert rung["live_GB"][next(k for k in rung["live_GB"]
                                if k.startswith(rung["taken"]))] < 14.4
    assert (cell["chips"], cell["global_batch"], cell["traffic_name"]) == \
        (1, 1, "steady")
    assert cell["seq_len"] == {"a": 16384, "b": 8192}[rung["taken"]]
    c = mod.build(cfg).config
    assert (c.hidden_size, c.dense_width, c.num_heads, c.linear_key_dim,
            c.linear_value_dim, c.conv_kernel, c.chunk_size,
            c.kda_lower_bound, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, c.kv_lora_rank, c.num_experts, c.experts_held,
            c.top_k, c.n_group, c.topk_group, c.expert_width,
            c.routed_scaling, c.vocab_size, c.num_layers,
            c.first_dense_layers, c.rope_theta) == \
        (2560, 6144, 16, 128, 128, 4, 64, -5.0, 128, 64, 128, 512, 512, 8,
         8, 8, 4, 768, 2.5, 19648, 7, 1, 6e6)
    assert [c.mixer_kind(i) for i in range(7)] == \
        ["linear_attention"] * 5 + ["attention", "linear_attention"]
    assert c.attention_config().attn_gate and c.moe_config().n_group == 8
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == 648_853_344


@pytest.mark.parametrize("key,value", [
    ("model_type", "olmo_hybrid"), ("hidden_act", "gelu"),
    ("score_function", "softmax"), ("topk_method", "greedy"),
    ("norm_topk_prob", False), ("kda_safe_gate", False),
    ("no_kda_lora", False), ("num_key_value_heads", 8),
    ("gated_attention_proj_granularity_type", "element_wise"),
    ("group_norm_size", 4), ("q_lora_rank", 1536),
    ("tie_word_embeddings", True), ("use_qkv_bias", True),
    ("first_k_dense_replace", 0), ("mtp_loss_scaling_factor", 0.3),
    ("kda_lower_bound", -12)])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, key, value):
    cfg = dict(cell["config"], **{key: value})
    if key == "mtp_loss_scaling_factor":
        cfg["num_nextn_predict_layers"] = 1
    with pytest.raises(ValueError):
        model = mod.build(cfg)
        if key == "kda_lower_bound":  # the mixer's own refusal
            model.init_params(jax.random.PRNGKey(0))


def test_build_refuses_a_swiglu_limit_among_the_kept_layers(cell, mod):
    limits = list(cell["config"]["expert_swiglu_limit_list"])
    assert not any(limits[:7]) and any(limits)
    limits[6] = 4
    with pytest.raises(ValueError, match="clamp"):
        mod.build(dict(cell["config"], expert_swiglu_limit_list=limits))


def test_operation_counts_against_hand_arithmetic(cell, mod):
    cfg, seq = cell["config"], cell["seq_len"]
    kda = 6 * (2560 * (4 * 2048 + 2 * 16) + 2048 * 2560)
    rule = 3 * 7 * 128 * 128 * 16
    latent = 6 * (2560 * 3072 + 2560 * 576 + 512 * 4096 + 2048 * 2560
                  + 2560 * 16)
    pairs = 3 * 2 * (192 + 128) * 16 * (seq + 1) / 2
    dense = 6 * 3 * 2560 * 6144
    sparse = 6 * (2560 * 512 + 3 * 2560 * 768 + 0.125 * 3 * 2560 * 768)
    head = 6 * 2560 * 19648
    assert mod.train_flops_per_token(cfg) == pytest.approx(
        6 * (kda + rule) + latent + pairs + dense + 6 * sparse + head,
        rel=1e-12)
    att = mod.attention_cost_per_step(cfg, 1)
    assert att["flops"] == 6 * (192 + 128) * (seq * (seq + 1) // 2) * 16
    rec = mod.delta_cost_per_step(cfg, 1)
    assert rec["flops"] == 6 * 3 * seq * 7 * 128 * 128 * 16
    # the decay moves dk numbers a head and token, not one
    assert rec["bytes"] == 6 * 2 * seq * 16 * (5 * 128 + 1) * 2
    moe = mod.moe_cost_per_step(cfg, 1)
    assert moe["flops"] == 6 * 9 * 2 * (seq // 8) * 2560 * 768


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    assert any(w["name"] == CELL for w in bench["workloads"])
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(LISTED)
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == list(LISTED[-3:])
    assert bench["per_layer"][-3:] == new  # appended, at the end
    for m in cell["per_layer"]:  # every reader the cell asks for loads
        reader = cells.load_module("layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])
        assert reader.read(None, [], {}, cell) is None


def test_the_readers_split_the_step_by_the_scopes_file(monkeypatch, cell,
                                                       mod):
    rules = program.part_rules("bailing_hybrid")
    assert list(rules) == ["optimizer", "head_loss", "linattn", "mlp",
                           "attn_dense"]
    top = "BailingHybrid/layers"
    la, at = f"{top}/linear_attention", f"{top}/attention"
    scopes = {f"fwd/{la}/q_proj": 3, f"fwd/{la}/f_proj": 5,
              f"bwd/{la}/decay": 7, f"fwd/{la}/conv": 11,
              f"bwd/{la}/delta": 70, f"fwd/{la}/g_proj": 2,
              f"bwd/{la}/gate": 4, f"fwd/{la}/gates/b_proj": 1,
              f"fwd/{la}/gate_norm": 6, f"fwd/{at}/q_proj": 13,
              f"fwd/{at}/kv_b_proj": 17, f"fwd/{at}/g_proj": 8,
              f"bwd/{at}/gate": 9, f"fwd/{at}/rope": 10,
              f"fwd/{top}/feed_forward/moe/experts": 19,
              f"fwd/{top}/feed_forward/moe/dispatch": 23,
              f"fwd/{top}/feed_forward/gate_proj": 29,
              f"fwd/{top}/input_norm": 31, "bwd/loss": 37,
              "optimizer": 41}
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

    assert read("step.linattn_ms") == 3 + 5 + 7 + 11 + 70 + 2 + 4 + 1 + 6
    assert read("step.linattn_scan_ms") == 11 + 70
    assert read("step.linattn_decay_ms") == 5 + 7
    assert read("step.attn_gate_ms") == 2 + 4 + 8 + 9  # both mixers'
    assert read("step.attn_latent_ms") == 17 + 10
    assert read("step.attn_dense_ms") == 13 + 17 + 8
    assert read("step.mlp_ms") == 19 + 23 + 29
    assert read("step.moe_experts_ms") == 19
    assert read("step.moe_route_ms") == 23
    assert read("step.unscoped_ms") == 9 + 10 + 31
    share = read("kernel.delta_roofline")
    cost = mod.delta_cost_per_step(cell["config"], cell["global_batch"])
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert share == pytest.approx(100 * least * 1e3 / 81.0)
    assert 0 < share < 100


@pytest.mark.parametrize("metric,counter", [
    ("linattn.decay_floor_share", "kda_decay_floor_share"),
    ("moe.group_limit_binds_share", "moe_group_limit_binds")])
def test_the_new_counter_readers_read_the_programs_counters(
        monkeypatch, cell, metric, counter):
    events = [{"ev": "open", "t": 1.0, "t_sync": 1.0, "gen": 0, "step": 10},
              {"ev": "close", "t": 9.0, "t_sync": 9.0, "gen": 0, "step": 20}]
    spans = [{"name": "trainer:step_metrics", "t_mono": t, "dur_s": 0.0,
              "attrs": {"step": 10, counter: share}}
             for t, share in ((0.5, 0.9), (2.0, 0.25), (5.0, 0.35))]
    monkeypatch.setattr(program, "setup_spans", lambda: spans)
    reader = cells.load_module("layer_metrics", metric)
    assert reader.read(None, events, {}, cell) == pytest.approx(30.0)
    # a program without the counter (the parent commit): nothing, no raise
    for s in spans:
        del s["attrs"][counter]
    assert reader.read(None, events, {}, cell) is None


def test_the_balanced_bias_evens_the_load_under_the_group_limit(mod):
    from dlrover_wuqiong_tpu.models.moe import expert_counts, route_top_k

    scores = jax.nn.sigmoid(1.5 * jax.random.normal(
        jax.random.PRNGKey(5), (4096, 64))
        + jnp.linspace(-1.0, 1.0, 64))  # a tilted router
    def load(bias):
        _, experts = route_top_k(scores, 4, bias=bias, floor=False,
                                 n_group=8, topk_group=4)
        n = expert_counts(experts, 64).astype(jnp.float32)
        return float(n.max() / n.mean())

    assert load(jnp.zeros(64)) > 2.0
    assert load(mod.balanced_bias(scores, 4, 8, 4)) < 1.1


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, intermediate_size=96,
               moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=32, num_experts=4,
               num_experts_per_tok=3, n_group=4, topk_group=2,
               qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
               rotary_dim=8, v_head_dim=16, kv_lora_rank=24,
               layer_group_size=3, num_hidden_layers=4,
               max_position_embeddings=64)
    cfg["share"] = dict(cfg["share"], num_experts_published=16,
                        first_expert=4)
    cfg["train"] = dict(cfg["train"], seq_len=64)
    cfg["program"] = dict(cfg["program"], dtype="float32",
                          use_flash_attention=False, delta_chunk_size=16)
    return cfg


def test_reference_matches_program_at_nano_f32(cell, mod):
    cfg = _nano(cell["config"])
    model = mod.build(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
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
    from benchmark import reference_bailing_hybrid

    for wrong in reference_bailing_hybrid.WRONG:
        off, off_norm = loss_and_grad_norm(
            mod.reference_loss(cfg, wrong=wrong), params, batch,
            precision="highest")
        # each wrong equation is another number (the group limit moves
        # few tokens' choice at sixteen experts: the weakest of them)
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
    assert read("layer_metrics", "linattn.padded_lanes_share") == 0.0
    assert 0.0 <= read("layer_metrics", "linattn.decay_floor_share") < 100.0
    assert 0.0 < read("layer_metrics", "moe.group_limit_binds_share") < 100.0
    assert 0.3 < read("layer_metrics", "attn.gate_mean") < 0.7
    assert 0.0 < read("layer_metrics", "moe.held_rows_share") < 100.0
