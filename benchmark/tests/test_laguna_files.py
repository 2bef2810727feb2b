"""The Laguna-XS.2 configuration: the catalog row's keys with exactly the
listed cuts, what `build` refuses, operation counts against hand
arithmetic (each layer at its OWN head count and its own kept pairs),
the cell's place in `BENCHMARK.json`, the readers on its scopes file and
counters, its plain reference against the program at a tiny size on the
CPU (both float32), and the cell's control flow rehearsed on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, program, worker
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

CELL = "laguna_xs_2_33b_a3b.steady"
NEW = ("step.attn_gate_ms", "attn.window_kept_share", "attn.gate_mean")
JOINED = ("step.moe_experts_ms", "step.moe_route_ms",
          "kernel.moe_gmm_roofline", "moe.load_max_over_mean",
          "moe.held_rows_share", "kernel.attn_window_ms",
          "kernel.attn_window_roofline", "attn.window_tiles_share")
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "num_attention_heads_per_layer", "num_experts", "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# no width may differ from the row, in the file or inside a nested group
WIDTHS = ("hidden_size", "intermediate_size", "head_dim",
          "moe_intermediate_size", "shared_expert_intermediate_size",
          "num_experts_per_tok", "num_key_value_heads", "sliding_window",
          "partial_rotary_factor", "rope_parameters")
FULL, SLIDING = "full_attention", "sliding_attention"


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "laguna")


@pytest.fixture(scope="module")
def row():
    """The catalog row `Laguna-XS.2` (the model-configs guide's)."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Laguna-XS.2")


def test_reduced_is_exactly_what_differs_from_the_catalog_row(cell, row):
    cfg, published = cell["config"], row["config"]
    assert cfg["source"] == row["source_url"]
    differs = [key for key, value in published.items()
               if cfg.get(key, "absent") != value]
    assert sorted(differs) == sorted(REDUCED)
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED and entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/laguna_xs_2_33b_a3b.json"
    for key in WIDTHS:
        assert cfg[key] == published[key] and key not in REDUCED, key
    # the cuts, each at the guide's floor or above: the leading dense
    # layer and one whole period of four, 32 of 256 experts held (ep =
    # 8), an eighth of the vocabulary
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert cfg[key] == published[key][:5], key
    assert cfg["layer_types"] == [FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (cfg["num_hidden_layers"], cfg["num_experts"]) == (5, 32)
    assert cfg["vocab_size"] * 8 == published["vocab_size"]
    assert cfg["vocab_size"] % 128 == 0


def test_the_share_the_assumptions_and_the_program_it_builds(cell, mod):
    cfg = cell["config"]
    share = cfg["share"]
    assert (share["num_experts_published"], share["first_expert"],
            share["chips_sharing_a_layer"], share["vocab_size_published"],
            share["vocabulary_slices"], share["num_hidden_layers_published"],
            share["pipeline_stages"]) == (256, 0, 8, 100352, 8, 40, 10)
    assert share["parameters"] == 691_623_936
    assert "691,623,936" in share["parameters_sum"]
    # each open point with the reading that was not taken
    for key in ("gating", "router", "qk_norm", "shared_expert_gate",
                "router_aux_loss_coef_origin"):
        text = cfg["assumed"][key]
        assert "eading not taken" in text or "readings not taken" in text, key
    for key in ("rotation", "initializer", "unused_keys"):
        assert cfg["assumed"][key], key
    assert "first of ten" in cfg["deployment"]
    assert "EIGHTH" in cfg["deployment"]
    assert (cell["chips"], cell["seq_len"], cell["global_batch"],
            cell["traffic_name"]) == (1, 16384, 1, "steady")
    rung = cfg["train"]["memory_rung"]
    assert rung["taken"] == "1 x 16384"
    assert 13.0 < rung["live_GB"]["1 x 16384"] < rung["limit_GB"] == 14.4
    c = mod.build(cfg).config
    assert (c.hidden_size, c.num_kv_heads, c.head_dim, c.expert_width,
            c.shared_width, c.dense_width, c.num_experts, c.top_k,
            c.experts_held, c.first_expert, c.vocab_size, c.sliding_window,
            c.routed_scaling, c.attn_gate, c.num_heads_per_layer) == \
        (2048, 8, 128, 512, 512, 8192, 256, 8, 32, 0, 12544, 512, 2.5, True,
         (48, 64, 64, 64, 48))
    full, sliding = c.attention_config(0), c.attention_config(1)
    assert (full.num_heads, full.attn_window, full.attn_gate,
            c.rotary_dim(FULL)) == (48, 0, True, 64)
    assert (sliding.num_heads, sliding.attn_window, sliding.attn_gate,
            c.rotary_dim(SLIDING)) == (64, 512, True, 128)
    assert (c.full_rope_theta, c.sliding_rope_theta) == (5e5, 1e4)
    yarn = c.full_rope_scaling
    assert (yarn.factor, yarn.original_max_position_embeddings,
            yarn.beta_fast, yarn.beta_slow) == (64.0, 4096, 64.0, 1.0)
    assert yarn.table_mscale == pytest.approx(
        cfg["rope_parameters"][FULL]["attention_factor"], rel=1e-15)
    moe = c.moe_config()
    assert (moe.expert_act, moe.score_func, moe.norm_topk_prob, moe.impl,
            moe.aux_loss, moe.held, moe.shared_width, moe.routed_scaling,
            moe.selection_bias) == \
        ("swiglu", "softmax", True, "grouped", "topk", 32, 512, 2.5, False)
    # the file's ASSUMED load-balancing term, the mean over the four
    # sparse layers
    assert cfg["assumed"]["router_aux_loss_coef"] == 0.01
    assert moe.aux_loss_weight == 0.01 / 4
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == share["parameters"]
    shapes = jax.eval_shape(mod.build(cfg).init_params,
                            jax.random.PRNGKey(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes)) \
        == share["parameters"]


def _nested(cfg, path, value):
    out = json.loads(json.dumps(cfg))
    node = out
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = value
    return out


@pytest.mark.parametrize("path,value", [
    (("model_type",), "llama"), (("gating",), "per-element"),
    (("gating",), False), (("attention_bias",), True),
    (("tie_word_embeddings",), True),
    (("moe_apply_router_weight_on_input",), True),
    (("layer_types",), [FULL, SLIDING, SLIDING, SLIDING]),
    (("layer_types",), [FULL, SLIDING, SLIDING, SLIDING, "linear"]),
    (("mlp_layer_types",), ["dense"] * 4 + ["moe"]),
    (("num_attention_heads_per_layer",), [48, 64, 64, 64, 44]),
    (("num_hidden_layers",), 4), (("partial_rotary_factor",), 1.0),
    (("rope_parameters", FULL, "rope_type"), "linear"),
    (("rope_parameters", SLIDING, "rope_type"), "yarn"),
    (("rope_parameters", FULL, "attention_factor"), 1.0),
    (("max_position_embeddings",), 8192),
    (("program", "impl"), "capacity")])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, path, value):
    with pytest.raises(ValueError):
        mod.build(_nested(cell["config"], path, value))


def test_operation_counts_are_a_sum_over_the_layers_own_heads(cell, mod):
    cfg = cell["config"]
    seq, win, d = 16384, 512, 128
    causal = seq * (seq + 1) // 2
    band = win * seq - win * (win - 1) // 2
    assert (mod.kept_pairs(seq, None), mod.kept_pairs(seq, win)) == \
        (causal, band)
    assert mod.kept_pairs(40, 7) == sum(min(i + 1, 7) for i in range(40))
    assert mod.kept_pairs(40, 40) == mod.kept_pairs(40, 99) == 40 * 41 // 2
    assert 0.06 < band / causal < 0.063  # a sliding layer keeps 6%
    # per token, forward + backward, 6 FLOPs a matmul parameter passed
    h, kv = 2048, 8 * d
    full = 2 * h * 48 * d + 2 * h * kv + h * 48
    sliding = 2 * h * 64 * d + 2 * h * kv + h * 64
    dense = 3 * h * 8192
    sparse = h * 256 + 3 * h * (1.0 * 512 + 512)  # 8 x 32 / 256 rows + shared
    head = h * 12544
    parts = mod.dense_params_per_token(cfg)
    assert parts["attention"] == 2 * full + 3 * sliding
    assert sum(parts.values()) == 2 * full + 3 * sliding + dense \
        + 4 * sparse + head
    pairs = 12 * d * (2 * 48 * causal + 3 * 64 * band) / seq
    assert mod.train_flops_per_token(cfg) == pytest.approx(
        6 * sum(parts.values()) + pairs, rel=1e-12)
    # the issue's split of a token's forward work: the kernels about
    # half, the attention about four fifths
    fwd = mod.train_flops_per_token(cfg) / 3
    assert 0.45 < pairs / 3 / fwd < 0.52
    assert 0.78 < (pairs / 3 + 2 * parts["attention"]) / fwd < 0.84
    att = mod.attention_cost_per_step(cfg, 1)
    assert att["flops"] == 6 * 2 * d * (2 * 48 * causal + 3 * 64 * band)
    # q, o, dO, dq once a query head OF THAT LAYER; k, v, dk, dv once a
    # key/value head
    assert att["bytes"] == 6 * (2 * (48 + 8) + 3 * (64 + 8)) * seq * d * 2
    # one head count for every layer would misread both
    assert att["flops"] != 6 * 2 * d * 64 * (2 * causal + 3 * band)
    assert att["flops"] != 6 * 2 * d * 48 * (2 * causal + 3 * band)
    local = mod.window_attention_cost_per_step(cfg, 2)
    assert local["flops"] == 2 * 6 * 2 * d * 3 * 64 * band
    assert local["bytes"] == 2 * 6 * 3 * (64 + 8) * seq * d * 2
    assert local["flops_fwd"] * 3 == local["flops"]
    # the band is so thin that the bytes take over half the operations'
    # time (24.7 ms against 13.3 at two sequences): still compute-bound
    assert 1.7 < (local["flops"] / 197e12) / (local["bytes"] / 819e9) < 2.0
    moe = mod.moe_cost_per_step(cfg, 1)
    rows = seq  # 8 x 32 / 256 = 1 row a token
    assert moe["flops"] == 4 * 9 * 2 * rows * 2048 * 512
    assert moe["bytes"] == 4 * (5 * rows * 2048 * 2
                                + 3 * 3 * 32 * 2048 * 512 * 2)


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert len(bench["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    entry = bench["workloads"][-1]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("laguna_xs_2_33b_a3b", "steady", 1)
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | set(JOINED)
    for m in bench["per_layer"]:
        if m["name"] in JOINED:
            assert m["workloads"][-1] == CELL
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "tokens_per_s"
    assert [m["name"] for m in bench["per_layer"]][-3:] == list(NEW)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | set(JOINED) <= names
    assert not names & {"step.collective_ms", "step.ssm_ms",
                        "step.attn_latent_ms", "step.resmix_ms"}
    for m in cell["per_layer"]:  # every reader the cell asks for loads
        reader = cells.load_module("layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])


@pytest.mark.parametrize("name", NEW)
def test_the_new_readers_return_none_on_nothing(name, cell):
    reader = cells.load_module("layer_metrics", name)
    assert reader.read(None, [], {}, cell) is None
    assert reader.read({}, [], {}, cell) is None
    other = dict(cell, config=dict(cell["config"], model_class="gpt"))
    assert reader.read({"devices": {}}, [], {}, other) is None


def test_the_readers_split_the_step_by_the_scopes_file(monkeypatch, cell,
                                                       mod):
    rules = program.part_rules("laguna")
    assert list(rules) == ["optimizer", "head_loss", "mlp", "attn_dense"]
    top = "Laguna/layers"
    table = {"fusion.1": f"fwd/{top}/feed_forward/moe/router",
             "fusion.2": f"fwd/{top}/feed_forward/moe/shared",
             "dwt_gmm.3": f"bwd/{top}/feed_forward/moe/experts/dwt_gmm",
             "fusion.4": f"fwd/{top}/feed_forward/gate_proj",
             "fusion.5": f"fwd/{top}/attention/q_proj",
             "fusion.6": f"bwd/{top}/attention/g_proj",
             "fusion.7": f"bwd/{top}/attention/gate",
             "fusion.8": f"fwd/{top}/attention/rope_partial",
             "fusion.9": "fwd/Laguna/head",
             "fusion.10": "bwd/loss", "fusion.11": "optimizer"}
    durs = {"fusion.1": 3e6, "fusion.2": 5e6, "dwt_gmm.3": 7e6,
            "fusion.4": 11e6, "fusion.5": 13e6, "fusion.6": 17e6,
            "fusion.7": 19e6, "fusion.8": 23e6, "fusion.9": 29e6,
            "fusion.10": 31e6, "fusion.11": 37e6,
            "dwt_fa_win_fwd.1": 40e6, "dwt_fa_win_bwd_fused.1": 60e6,
            "dwt_fa_fwd.2": 50e6}
    ops, t = [], 0
    for name, dur in durs.items():
        ops.append([name, t, dur])
        t += dur
    trace = {"devices": {"0": {"modules": [["jit_train_step(1)", 0, t]],
                               "ops": ops}}, "host": []}
    monkeypatch.setattr(program, "_table", table)
    ledgers = {0: {"device": {"kind": "TPU v5 lite"}}}

    def read(name):
        return cells.load_module("layer_metrics", name).read(
            trace, [], ledgers, cell)

    assert read("step.mlp_ms") == 3.0 + 5.0 + 7.0 + 11.0  # dense layer too
    assert read("step.moe_experts_ms") == 7.0
    assert read("step.moe_route_ms") == 3.0 + 5.0  # router and shared
    assert read("step.attn_dense_ms") == 13.0 + 17.0  # the gate's product
    assert read("step.unscoped_ms") == 19.0 + 23.0
    # the overlay: the product (in attn_dense) and the rest (unscoped)
    assert read("step.attn_gate_ms") == 17.0 + 19.0
    assert read("kernel.attn_ms") == 150.0
    assert read("kernel.attn_window_ms") == 100.0
    cost = mod.window_attention_cost_per_step(cell["config"],
                                              cell["global_batch"])
    assert read("kernel.attn_window_roofline") == pytest.approx(
        100 * cost["flops"] / 197e12 * 1e3 / 100.0)
    # a step without a gate's scopes (the parent's program): left out
    monkeypatch.setattr(program, "_table", {
        k: v for k, v in table.items() if "/g" not in v})
    assert read("step.attn_gate_ms") is None
    gated = json.load(open(os.path.join(
        cells.HERE, "models", "laguna.scopes.json")))
    assert list(gated["gate_parts"]) == ["attn_gate"]
    assert list(gated["rope_parts"]) == ["rope_partial"]


def test_the_two_counters_read_the_steps_events(monkeypatch, cell):
    events = [{"ev": "open", "t_sync": 10.0, "step": 20},
              {"ev": "close", "t_sync": 20.0, "step": 30}]
    kept, held = 64 * (512 * 16384 - 512 * 511 // 2), 64 * 63 * 512 * 512
    spans = [{"name": "trainer:step_metrics", "t_mono": t,
              "attrs": {"step": 1, "attn_pairs_kept": 3.0 * kept,
                        "attn_pairs_computed": 3.0 * held,
                        "attn_gate_mean": mean}}
             for t, mean in ((5.0, 0.9), (12.0, 0.5), (18.0, 0.4))] + [
        {"name": "trainer:step_metrics", "t_mono": 15.0,
         "attrs": {"step": 2, "moe_dropped": 0.0}}]
    monkeypatch.setattr(program, "setup_spans", lambda: spans)
    share = cells.load_module("layer_metrics", "attn.window_kept_share")
    gate = cells.load_module("layer_metrics", "attn.gate_mean")
    assert share.read(None, events, {}, cell) == pytest.approx(
        100 * kept / held)
    assert 49.0 < share.read(None, events, {}, cell) < 51.0
    assert gate.read(None, events, {}, cell) == pytest.approx(0.45)
    # the parent's program counts neither
    monkeypatch.setattr(program, "setup_spans", lambda: spans[-1:])
    assert share.read(None, events, {}, cell) is None
    assert gate.read(None, events, {}, cell) is None


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, intermediate_size=96,
               num_attention_heads_per_layer=[6, 8, 8, 8, 6],
               num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
               shared_expert_intermediate_size=32, num_experts_per_tok=3,
               num_experts=4, sliding_window=24, max_position_embeddings=64)
    cfg["rope_parameters"][FULL].update(
        rope_theta=100, factor=16, original_max_position_embeddings=16,
        beta_fast=2, beta_slow=0.25, attention_factor=0.1 * 2.772588722239781
        + 1.0)
    cfg["share"] = dict(cfg["share"], num_experts_published=8,
                        first_expert=2)
    cfg["train"] = dict(cfg["train"], seq_len=64)
    cfg["program"] = dict(cfg["program"], dtype="float32",
                          use_flash_attention=False)
    return cfg


def test_reference_matches_program_at_nano_f32(cell, mod):
    cfg = _nano(cell["config"])
    model = mod.build(cfg)
    params = model.init_params(jax.random.PRNGKey(3), seq=64)
    batch = {k: jnp.asarray(v) for k, v in
             make_data(256, 4, 64, seed=3)(0).items()}
    from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

    sys_loss, sys_norm = loss_and_grad_norm(make_lm_loss(model.apply),
                                            params, batch)
    ref_loss, ref_norm = loss_and_grad_norm(mod.reference_loss(cfg),
                                            params, batch,
                                            precision="highest")
    # float32 on both sides: only the order of sums differs
    assert abs(sys_loss - ref_loss) / ref_loss < 1e-5
    assert abs(sys_norm - ref_norm) / ref_norm < 1e-4
    # a wrong-equation control: the gate dropped
    ungated = loss_and_grad_norm(
        mod.reference_loss(cfg, wrong=("gate",)), params, batch,
        precision="highest")
    assert abs(ungated[1] - ref_norm) / ref_norm > 1e-3


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

    def read(name):
        return cells.load_module("layer_metrics", name).read(
            None, run["events"], {0: rec}, cell)

    assert cells.load_module("end_to_end", "tokens_per_s").read(
        None, run["events"], {0: rec}, cell) > 0
    assert 0 < read("attn.window_kept_share") <= 100
    assert 0 < read("attn.window_tiles_share") <= 100
    assert 0 < read("moe.held_rows_share") < 100
    assert 0.2 < read("attn.gate_mean") < 0.8
