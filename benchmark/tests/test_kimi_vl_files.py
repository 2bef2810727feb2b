"""The Kimi-VL-A3B language-model configuration: published widths and
the three cuts, what `build` refuses, operation counts against hand
arithmetic (QK^T over 192 lanes, PV over 128), the readers on its scopes
file and counters (each returns None on nothing), its plain reference
against the program at a tiny size on the CPU (both float32), and the
cell's control flow rehearsed on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, program, worker
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

CELL = "kimi_vl_a3b.steady"
NEW = ("step.attn_latent_ms", "attn.padded_lanes_share")
JOINED = ("step.moe_experts_ms", "step.moe_route_ms",
          "kernel.moe_gmm_roofline", "moe.load_max_over_mean",
          "moe.held_rows_share")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
# the catalog row Kimi-VL-A3B-Instruct (model-configs guide): its `config`
CATALOG = {
    "vocab_size": 163840, "max_position_embeddings": 131072,
    "hidden_size": 2048, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "num_hidden_layers": 27,
    "num_attention_heads": 16, "n_shared_experts": 2,
    "n_routed_experts": 64, "ep_size": 1, "routed_scaling_factor": 2.446,
    "kv_lora_rank": 512, "q_lora_rank": None, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "qk_nope_head_dim": 128, "topk_method": "noaux_tc",
    "n_group": 1, "topk_group": 1, "num_experts_per_tok": 6,
    "moe_layer_freq": 1, "first_k_dense_replace": 1,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "seq_aux": True,
    "num_key_value_heads": 16, "hidden_act": "silu", "rms_norm_eps": 1e-05,
    "rope_theta": 800000, "rope_scaling": None, "attention_bias": False,
    "tie_word_embeddings": False}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "kimi_vl")


def test_widths_are_the_catalog_rows_and_three_cuts_are_listed(cell, mod):
    cfg = cell["config"]
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED and entry["source"] == cfg["source"]
    for key, published in CATALOG.items():
        if key not in REDUCED:
            assert cfg[key] == published, key
    # the cuts, each at the guide's floor: the leading dense layer and
    # four behind it, 8 of 64 experts held, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    assert cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    share = cfg["share"]
    assert (share["n_routed_experts_published"], share["first_expert"],
            share["chips_sharing_a_layer"], share["ep"],
            share["vocab_size_published"], share["vocabulary_slices"],
            share["num_hidden_layers_published"]) == \
        (64, 0, 8, 8, 163840, 8, 27)
    assert share["parameters"] == 568_484_608
    assert 82_973_184 + 4 * 100_405_824 + 83_888_128 == 568_484_608
    assert "568,484,608" in share["parameters_sum"]
    assert share["whole_expert_layer_parameters"] == 584_847_936
    for key in ("selection_bias", "seq_aux", "rope_pairing", "tower",
                "attention", "expert_form", "router", "initializer",
                "unused_keys"):
        assert cfg["assumed"][key], key
    assert "first pipeline stage" in cfg["deployment"]
    assert "EIGHTH" in cfg["deployment"]
    rung = cfg["train"]["memory_rung"]
    assert set(rung["live_GB"]) == {"1 x 16384 at depth 5",
                                    "2 x 16384 at depth 5",
                                    "1 x 16384 at depth 6"}
    assert rung["live_GB"][rung["taken"]] < rung["limit_GB"] == 14.4
    assert (cell["chips"], cell["seq_len"], cell["traffic_name"],
            cell["global_batch"]) == (1, 16384, "steady", 2)
    c = mod.build(cfg).config
    assert (c.hidden_size, c.num_heads, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank, c.q_lora_rank,
            c.dense_width, c.first_dense_layers, c.num_layers,
            c.expert_width, c.shared_experts, c.num_experts, c.top_k,
            c.experts_held, c.first_expert, c.vocab_size, c.rope_theta,
            c.routed_scaling, c.max_seq_len) == \
        (2048, 16, 128, 64, 128, 512, None, 11264, 1, 5, 1408, 2, 64, 6, 8,
         0, 20480, 8e5, 2.446, 131072)
    moe = c.moe_config()
    assert (moe.expert_act, moe.score_func, moe.selection_bias,
            moe.norm_topk_prob, moe.impl, moe.aux_loss, moe.held,
            moe.shared_width, moe.bias_update_rate) == \
        ("swiglu", "sigmoid", True, True, "grouped", "none", 8, 2816, 0.05)
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == 568_484_608


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "relu2"), ("scoring_func", "softmax"),
    ("topk_method", "group_limited_greedy"), ("n_group", 8),
    ("topk_group", 4), ("moe_layer_freq", 2),
    ("rope_scaling", {"type": "yarn"}), ("attention_bias", True),
    ("tie_word_embeddings", True), ("num_key_value_heads", 4),
    ("first_k_dense_replace", 0), ("first_k_dense_replace", 6),
    ("norm_topk_prob", False)])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, key, value):
    with pytest.raises(ValueError):
        mod.build(dict(cell["config"], **{key: value}))


def test_a_q_latent_is_refused_by_the_program_itself(cell, mod):
    model = mod.build(dict(cell["config"], q_lora_rank=1536))
    with pytest.raises(ValueError, match="q_lora_rank"):
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0))


def test_operation_counts_against_hand_arithmetic(cell, mod):
    cfg = cell["config"]
    seq = 16384
    causal = seq * (seq + 1) // 2
    # forward FLOPs a token at 2 x 16,384: the attention kernels' two
    # products 5 x 16,384 x 5,120 = 419M; everything else 551M
    pairs = mod.attention_pairs_flops_per_token(cfg)
    assert pairs == 5 * (seq + 1) * 5120
    assert pairs == pytest.approx(419e6, rel=2e-3)
    parts = mod.dense_params_per_token(cfg)
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert parts == {
        "attention": 5 * attn, "dense": 3 * 2048 * 11264,
        "router": 4 * 2048 * 64, "shared": 4 * 3 * 2048 * 2816,
        "routed": 4 * 0.75 * 3 * 2048 * 1408, "head": 2048 * 20480}
    rest = 2 * sum(parts.values())
    assert rest == pytest.approx(551e6, rel=2e-3)
    assert 2 * parts["attention"] == pytest.approx(138e6, rel=5e-3)
    assert 2 * parts["dense"] == pytest.approx(138e6, rel=5e-3)
    assert 2 * (parts["router"] + parts["shared"] + parts["routed"]) \
        == pytest.approx(192e6, rel=5e-3)
    assert 2 * parts["routed"] == pytest.approx(52e6, rel=5e-3)
    assert 2 * parts["head"] == pytest.approx(84e6, rel=5e-3)
    # the kernels 43% of the step's FLOPs, latent attention with its
    # projections 57%
    assert pairs / (pairs + rest) == pytest.approx(0.43, abs=0.005)
    assert (pairs + 2 * parts["attention"]) / (pairs + rest) \
        == pytest.approx(0.57, abs=0.006)
    assert mod.train_flops_per_token(cfg) == 3 * (pairs + rest)
    assert 3 * (pairs + rest) * 2 * seq == pytest.approx(95e12, rel=0.01)
    att = mod.attention_cost_per_step(cfg, 2)
    # S, dQ, dK over 192 lanes a kept pair, O, dV, dP over 128
    assert att["flops_fwd"] == 2 * 5 * 16 * causal * 2 * (192 + 128)
    assert att["flops_bwd"] == 2 * 5 * 16 * causal * 2 * (192 * 2 + 128 * 2)
    assert att["flops"] == att["flops_fwd"] + att["flops_bwd"]
    # q, k, dq, dk at 192 and v, o, dO, dv at 128, bf16, a head: forward
    # q k v o, backward q k v o dO dq dk dv
    one = 2 * 5 * 16 * seq * 2
    assert att["bytes_fwd"] == one * (192 + 192 + 128 + 128)
    assert att["bytes_bwd"] == one * (4 * 192 + 4 * 128)
    assert att["bytes"] == att["bytes_fwd"] + att["bytes_bwd"]
    # compute-bound: 209 ms of operations against 12 ms of bytes
    assert 16 < (att["flops"] / 197e12) / (att["bytes"] / 819e9) < 18
    moe = mod.moe_cost_per_step(cfg, 2)
    rows = int(2 * seq * 0.75)
    assert moe["flops"] == 4 * 9 * 2 * rows * 2048 * 1408
    assert moe["bytes"] == 4 * (5 * rows * 2048 * 2
                                + 3 * 3 * 8 * 2048 * 1408 * 2)


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    assert len(bench["workloads"]) >= 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(bench["workloads"]) // 4)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("kimi_vl_a3b", "steady", 1)
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | set(JOINED)
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | set(JOINED) | {"kernel.attn_ms",
                                     "kernel.attn_roofline"} <= names
    assert not names & {"step.collective_ms", "step.ssm_ms",
                        "step.ssm_scan_ms", "kernel.ssd_roofline",
                        "kernel.attn_window_ms", "attn.window_tiles_share"}
    for m in cell["per_layer"]:  # every reader the cell asks for loads
        reader = cells.load_module("layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])


def test_the_readers_split_the_step_by_the_scopes_file(monkeypatch, cell,
                                                       mod):
    rules = program.part_rules("kimi_vl")
    assert list(rules) == ["optimizer", "head_loss", "mlp", "attn_dense"]
    top = "LatentMoE/layers"
    table = {"fusion.1": f"fwd/{top}/feed_forward/moe/router",
             "fusion.2": f"fwd/{top}/feed_forward/moe/shared",
             "dwt_gmm.3": f"bwd/{top}/feed_forward/moe/experts/dwt_gmm",
             "fusion.4": f"bwd/{top}/feed_forward/gate_proj",
             "fusion.5": f"fwd/{top}/attention/q_proj",
             "fusion.6": f"bwd/{top}/attention/o_proj",
             "fusion.7": f"fwd/{top}/attention/kv_a_proj",
             "fusion.8": f"bwd/{top}/attention/kv_b_proj",
             "fusion.9": f"fwd/{top}/attention/kv_a_norm",
             "fusion.10": f"fwd/{top}/attention/rope",
             "fusion.11": f"bwd/{top}/attention/assemble",
             "fusion.12": f"fwd/{top}/attention",
             "fusion.13": f"fwd/{top}/input_norm",
             "fusion.14": "fwd/LatentMoE/head",
             "fusion.15": "bwd/loss", "fusion.16": "optimizer"}
    durs = {f"fusion.{i}": float(i) * 1e6 for i in (1, 2, *range(4, 17))}
    durs.update({"dwt_gmm.3": 3e6, "dwt_fa_fwd.1": 40e6,
                 "dwt_fa_bwd_dq.1": 60e6, "dwt_fa_bwd_dkv.2": 50e6})
    ops, t = [], 0
    for name, dur in durs.items():
        ops.append([name, t, dur])
        t += dur
    trace = {"devices": {"0": {"modules": [["jit_train_step(1)", 0, t]],
                               "ops": ops}}, "host": []}
    monkeypatch.setattr(program, "_table", table)
    ledgers = {0: {"device": {"kind": "TPU v5 lite"}}}

    def read(name, trace=trace):
        return cells.load_module("layer_metrics", name).read(
            trace, [], ledgers, cell)

    assert read("step.mlp_ms") == 1.0 + 2.0 + 3.0 + 4.0  # layer 0's too
    assert read("step.moe_experts_ms") == 3.0
    assert read("step.moe_route_ms") == 1.0 + 2.0  # the shared expert's
    assert read("step.attn_dense_ms") == 5.0 + 6.0 + 7.0 + 8.0
    assert read("step.head_loss_ms") == 14.0 + 15.0
    assert read("step.optimizer_ms") == 16.0
    assert read("step.unscoped_ms") == 9.0 + 10.0 + 11.0 + 12.0 + 13.0
    # the overlay: two of attn_dense's and three of unscoped's
    assert read("step.attn_latent_ms") == 7.0 + 8.0 + 9.0 + 10.0 + 11.0
    assert read("kernel.attn_ms") == 150.0
    cost = mod.attention_cost_per_step(cell["config"], cell["global_batch"])
    assert read("kernel.attn_roofline") == pytest.approx(
        100 * cost["flops"] / 197e12 * 1e3 / 150.0)
    # the five parts and the kernels are the step
    parts = sum(read(f"step.{p}_ms") for p in (
        "mlp", "attn_dense", "head_loss", "optimizer", "unscoped"))
    assert parts + read("kernel.attn_ms") == pytest.approx(t / 1e6)
    # nothing to read: no trace, or no op under the scopes
    assert read("step.attn_latent_ms", None) is None
    trace["devices"]["0"]["ops"] = [o for o in ops if o[0] in (
        "fusion.5", "fusion.16")]
    assert read("step.attn_latent_ms") is None
    # a class whose scopes file names no `attn_parts`
    other = dict(cell, config=dict(cell["config"], model_class="olmoe"))
    assert cells.load_module("layer_metrics", "step.attn_latent_ms").read(
        trace, [], ledgers, other) is None
    monkeypatch.setattr(program, "_table", None)
    monkeypatch.setattr(program, "scope_table", lambda: None)
    assert read("step.attn_latent_ms") is None


def test_the_padded_lanes_share_reads_the_steps_counters(monkeypatch, cell):
    events = [{"ev": "open", "t_sync": 10.0, "step": 20},
              {"ev": "close", "t_sync": 20.0, "step": 30}]

    def spans(run, model=5 * 320.0):
        return [{"name": "trainer:step_metrics", "t_mono": t,
                 "attrs": {"step": 1, "attn_lanes_run": run,
                           "attn_lanes_model": model}}
                for t in (5.0, 12.0, 18.0)] + [
            {"name": "trainer:step_metrics", "t_mono": 15.0,
             "attrs": {"step": 2, "moe_dropped": 0.0}}]

    share = cells.load_module("layer_metrics", "attn.padded_lanes_share")
    monkeypatch.setattr(program, "setup_spans", lambda: spans(5 * 320.0))
    assert share.read(None, events, {}, cell) == 0.0  # 192 / 128 natively
    monkeypatch.setattr(program, "setup_spans", lambda: spans(5 * 384.0))
    assert share.read(None, events, {}, cell) == pytest.approx(100 / 6)
    monkeypatch.setattr(program, "setup_spans", lambda: spans(5 * 512.0))
    assert share.read(None, events, {}, cell) == pytest.approx(37.5)
    # a program without the counters, a model without a latent layer
    monkeypatch.setattr(program, "setup_spans",
                        lambda: spans(5 * 320.0)[-1:])
    assert share.read(None, events, {}, cell) is None
    monkeypatch.setattr(program, "setup_spans", lambda: [])
    assert share.read(None, events, {}, cell) is None
    assert share.read(None, [], {}, cell) is None  # no window at all


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_hidden_layers=3,
               num_attention_heads=4, num_key_value_heads=4,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               kv_lora_rank=24, num_experts_per_tok=3, n_routed_experts=4,
               max_position_embeddings=64)
    cfg["share"] = dict(cfg["share"], n_routed_experts_published=8,
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
    # the wrong-equation controls move the gradient's norm
    for wrong in (dict(scale=16 ** -0.5), dict(latent_norm=False),
                  dict(rotate_key=False)):
        other = loss_and_grad_norm(mod.reference_loss(cfg, **wrong), params,
                                   batch, precision="highest")
        assert abs(other[1] - ref_norm) / ref_norm > 1e-4, wrong


def test_the_cells_control_flow_runs_on_the_cpu_at_nano_size(
        cell, monkeypatch, tmp_path):
    """The worker's own `train_process` — the seeded state with its
    balanced selection biases, the check against the reference through
    the Trainer's compiled step, the window — on the CPU at a toy size.
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
    assert cells.load_module("end_to_end", "tokens_per_s").read(
        None, run["events"], {0: rec}, cell) > 0
    lanes = cells.load_module("layer_metrics", "attn.padded_lanes_share")
    held = cells.load_module("layer_metrics", "moe.held_rows_share")
    assert lanes.read(None, run["events"], {0: rec}, cell) == 0.0
    assert 0 < held.read(None, run["events"], {0: rec}, cell) < 100
