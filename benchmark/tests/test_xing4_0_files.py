"""The Xing4.0-29B-A4B configuration: published widths and the five
cuts, what `build` refuses, operation and byte counts against hand
arithmetic (the q latent's two products, a hyper-connection's, the
mixing's least bytes), the readers on its scopes file and counters (each
returns None on nothing), its plain reference against the program at a
tiny size on the CPU (both float32), and the cell's control flow
rehearsed on the CPU."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, program, worker
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

CELL = "xing4_0_29b_a4b.steady"
NEW = ("step.resmix_ms", "step.resmix_sinkhorn_ms", "resmix.hbm_roofline",
       "resmix.sinkhorn_err")
JOINED = ("step.moe_experts_ms", "step.moe_route_ms",
          "kernel.moe_gmm_roofline", "moe.load_max_over_mean",
          "moe.held_rows_share", "step.attn_latent_ms",
          "attn.padded_lanes_share")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]
# the catalog row Xing4.0-29B-A4B (model-configs guide): its `config`
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "xing4_0")


def test_widths_are_the_catalog_rows_and_five_cuts_are_listed(cell, mod):
    cfg = cell["config"]
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED and entry["source"] == cfg["source"]
    assert cfg["source"].endswith("Xing4.0-29B-A4B/blob/main/config.json")
    for key, published in CATALOG.items():
        if key not in REDUCED:
            assert cfg[key] == published, key
    # the cuts, each at the guide's floor: the leading dense layers once
    # and four behind them, 8 of 64 experts held, an eighth of the table
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (5, 1)
    assert cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert cfg["num_nextn_predict_layers"] == 0
    share = cfg["share"]
    assert (share["n_routed_experts_published"], share["first_expert"],
            share["chips_sharing_a_layer"], share["ep"],
            share["vocab_size_published"], share["vocabulary_slices"],
            share["num_hidden_layers_published"],
            share["first_k_dense_replace_published"],
            share["num_nextn_predict_layers_published"]) == \
        (64, 0, 8, 8, 131072, 8, 40, 2, 1)
    assert share["parameters"] == 759_346_446
    attention = 3584 * 768 + 768 + 768 * 6144 + 3584 * 576 + 512 \
        + 512 * 8192 + 4096 * 3584
    block = attention + 2 * 3584 + 2 * (14336 * 24 + 27)
    assert attention == 28_411_136 and block == 29_106_486
    dense, expert = 3 * 3584 * 9216, 3 * 3584 * 1024
    assert block + dense + 4 * (block + 3584 * 64 + 64 + 9 * expert) \
        + 2 * 16384 * 3584 + 3584 == 759_346_446
    assert block + dense == 128_196_918
    for part in ("128,196,918", "4 x 128,426,358", "117,444,096",
                 "759,346,446"):
        assert part in share["parameters_sum"], part
    assert share["whole_expert_layer_parameters"] == \
        block + 3584 * 64 + 64 + 65 * expert == 744_989_046
    for key in ("read_out", "mixing_norm", "hyper_connection", "sinkhorn",
                "hyper_connection_init", "mtp", "selection_bias", "seq_aux",
                "rope_pairing", "yarn", "attention", "expert_form", "router",
                "initializer", "unused_keys"):
        assert cfg["assumed"][key], key
    assert "first pipeline stage" in cfg["deployment"]
    assert "EIGHTH" in cfg["deployment"]
    rung = cfg["train"]["memory_rung"]
    assert set(rung["live_GB"]) == {"1 x 8192", "1 x 4096"}
    assert rung["live_GB"][rung["taken"]] < rung["limit_GB"] == 14.4
    assert (cell["chips"], cell["seq_len"], cell["traffic_name"],
            cell["global_batch"]) == (1, 8192, "steady", 1)
    c = mod.build(cfg).config
    assert (c.hidden_size, c.num_heads, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank, c.q_lora_rank,
            c.dense_width, c.first_dense_layers, c.num_layers,
            c.expert_width, c.shared_experts, c.num_experts, c.top_k,
            c.experts_held, c.first_expert, c.vocab_size, c.rope_theta,
            c.routed_scaling, c.max_seq_len, c.residual_lanes,
            c.hc_sinkhorn_iters, c.hc_eps, c.hc_res_clamp, c.mtp_layers,
            c.rms_eps) == \
        (3584, 32, 128, 64, 128, 512, 768, 9216, 1, 5, 1024, 1, 64, 4, 8, 0,
         16384, 1e4, 2, 262144, 4, 20, 1e-6, (-30.0, 30.0), 0, 1e-6)
    assert (c.rope_scaling.factor, c.rope_scaling.beta_fast,
            c.rope_scaling.original_max_position_embeddings) == (64, 32, 4096)
    assert c.attention_config().attn_scale == pytest.approx(
        192 ** -0.5 * 1.41589 ** 2, rel=1e-5)
    moe = c.moe_config()
    assert (moe.expert_act, moe.score_func, moe.selection_bias,
            moe.norm_topk_prob, moe.impl, moe.aux_loss, moe.held,
            moe.shared_width, moe.bias_update_rate) == \
        ("swiglu", "sigmoid", True, True, "grouped", "none", 8, 1024, 0.05)
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == 759_346_446


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "relu2"), ("scoring_func", "softmax"),
    ("topk_method", "group_limited_greedy"), ("n_group", 8),
    ("topk_group", 4), ("moe_layer_freq", 2), ("attention_bias", True),
    ("rope_scaling", {"type": "linear", "factor": 4.0}),
    ("tie_word_embeddings", True), ("num_key_value_heads", 4),
    ("first_k_dense_replace", 0), ("first_k_dense_replace", 6),
    ("norm_topk_prob", False), ("num_nextn_predict_layers", 2)])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, key, value):
    with pytest.raises(ValueError):
        mod.build(dict(cell["config"], **{key: value}))


def test_operation_and_byte_counts_against_hand_arithmetic(cell, mod):
    cfg = cell["config"]
    seq = 8192
    pairs = mod.attention_pairs_flops_per_token(cfg)
    assert pairs == 5 * (seq + 1) * 32 * 320
    parts = mod.dense_params_per_token(cfg)
    assert parts["attention"] == 5 * (
        3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584)
    assert parts["dense"] == 3 * 3584 * 9216
    assert parts["router"] == 4 * 3584 * 64
    assert parts["shared"] == 4 * 3 * 3584 * 1024
    assert parts["routed"] == 4 * 0.5 * 3 * 3584 * 1024  # 4 x 8 / 64 rows
    assert parts["head"] == 3584 * 16384
    # a hyper-connection: the (4 x 3584) x 24 product and the two mixes'
    # 4 + 16 + 4 multiply-adds a hidden feature; ten of them
    assert parts["mixing"] == 10 * (14336 * 24 + 24 * 3584) == 4_300_800
    assert mod.train_flops_per_token(cfg) == \
        6.0 * sum(parts.values()) + 3.0 * pairs
    assert mod.train_flops_per_token(cfg) == pytest.approx(3.49e9, rel=0.01)
    # the mixing's least bytes: (3 x 4 + 2) hidden vectors a token in
    # bf16, ten sublayers, forward + recomputed forward + 2 x backward
    nbytes = mod.resmix_bytes_per_step(cfg, 1)
    assert nbytes == 4 * 10 * 8192 * 14 * 3584 * 2 == 32_883_343_360
    assert mod.resmix_bytes_per_step(cfg, 2) == 2 * nbytes
    moe = mod.moe_cost_per_step(cfg, 1)
    rows = 8192 * 4 * 8 // 64
    assert moe["flops"] == 4 * 9 * 2 * rows * 3584 * 1024


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    assert len(bench["workloads"]) >= 9
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("xing4_0_29b_a4b", "steady", 1)
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | set(JOINED)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["layer"] == "residual path"
            assert m["moves"] == "tokens_per_s"
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | set(JOINED) | {"kernel.attn_ms", "step.unowned_ms",
                                     "device.mfu_pct"} <= names
    assert not names & {"step.collective_ms", "step.ssm_ms",
                        "kernel.attn_window_ms", "step.linattn_ms"}
    for m in cell["per_layer"]:  # every reader the cell asks for loads
        reader = cells.load_module("layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])


def test_the_readers_split_the_step_by_the_scopes_file(monkeypatch, cell,
                                                       mod):
    rules = program.part_rules("xing4_0")
    assert list(rules) == ["optimizer", "head_loss", "mlp", "attn_dense"]
    top = "LatentMoE/layers"
    table = {"fusion.1": f"fwd/{top}/feed_forward/moe/router",
             "dwt_gmm.2": f"bwd/{top}/feed_forward/moe/experts/dwt_gmm",
             "fusion.3": f"fwd/{top}/attention/q_a_proj",
             "fusion.4": f"bwd/{top}/attention/q_b_proj",
             "fusion.5": f"fwd/{top}/attention/q_a_norm",
             "fusion.6": f"fwd/{top}/attention/kv_b_proj",
             "fusion.7": f"fwd/{top}/hc/coeff",
             "fusion.8": f"recompute/{top}/hc/sinkhorn",
             "fusion.9": f"bwd/{top}/hc/sinkhorn",
             "fusion.10": f"fwd/{top}/hc/pre",
             "fusion.11": f"bwd/{top}/hc/post_res",
             "fusion.12": "fwd/LatentMoE/hc/expand",
             "fusion.13": f"fwd/{top}/input_norm",
             "fusion.14": "fwd/LatentMoE/head",
             "fusion.15": "bwd/loss", "fusion.16": "optimizer"}
    durs = {f"fusion.{i}": float(i) * 1e6 for i in (1, *range(3, 17))}
    durs.update({"dwt_gmm.2": 2e6, "dwt_fa_fwd.1": 40e6})
    ops, t = [], 0
    for name, dur in durs.items():
        ops.append([name, t, dur])
        t += dur
    trace = {"devices": {"0": {"modules": [["jit_train_step(1)", 0, t]],
                               "ops": ops}}, "host": []}
    monkeypatch.setattr(program, "_table", table)
    ledgers = {0: {"device": {"kind": "TPU v5 lite"}}}

    def read(name, trace=trace, cell=cell):
        return cells.load_module("layer_metrics", name).read(
            trace, [], ledgers, cell)

    assert read("step.mlp_ms") == 1.0 + 2.0
    assert read("step.attn_dense_ms") == 3.0 + 4.0 + 6.0
    assert read("step.attn_latent_ms") == 6.0  # not the q latent's
    # the mixing is an overlay over `unscoped`
    assert read("step.unscoped_ms") == 5.0 + sum(range(7, 14))
    assert read("step.resmix_sinkhorn_ms") == 8.0 + 9.0
    assert read("step.resmix_ms") == sum(range(7, 13))
    least_ms = mod.resmix_bytes_per_step(cell["config"], 1) / 819e9 * 1e3
    assert read("resmix.hbm_roofline") == pytest.approx(
        100 * least_ms / sum(range(7, 13)))
    parts = sum(read(f"step.{p}_ms") for p in (
        "mlp", "attn_dense", "head_loss", "optimizer", "unscoped"))
    assert parts + read("kernel.attn_ms") == pytest.approx(t / 1e6)
    # Sinkhorn's rounds are a `while` in the step, and a trace holds the
    # loop's own span beside the ops inside it: the accepted split counts
    # both, the mixing's readers the ops alone
    table["while.17"] = f"bwd/{top}/hc/sinkhorn"
    start = next(o[1] for o in ops if o[0] == "fusion.8")
    looped = {"devices": {"0": {
        "modules": trace["devices"]["0"]["modules"],
        "ops": sorted(ops + [["while.17", start, 17e6]],
                      key=lambda o: o[1])}}, "host": []}
    assert read("step.unscoped_ms", looped) == 5.0 + sum(range(7, 14)) + 17
    assert read("step.resmix_sinkhorn_ms", looped) == 8.0 + 9.0
    assert read("step.resmix_ms", looped) == sum(range(7, 13))
    # nothing to read: no trace, or no op under the scopes
    for name in NEW[:3]:
        assert read(name, None) is None
    trace["devices"]["0"]["ops"] = [o for o in ops if o[0] in (
        "fusion.3", "fusion.16")]
    for name in NEW[:3]:
        assert read(name) is None
    # a class whose scopes file names no `resmix_parts`, whose model file
    # counts no such bytes: the parent's program in an accepted cell
    other = dict(cell, config=dict(cell["config"], model_class="kimi_vl"))
    trace["devices"]["0"]["ops"] = ops
    for name in NEW[:3]:
        assert read(name, cell=other) is None
    monkeypatch.setattr(program, "_table", None)
    monkeypatch.setattr(program, "scope_table", lambda: None)
    for name in NEW[:3]:
        assert read(name) is None


def test_the_sinkhorn_err_reads_the_steps_counter(monkeypatch, cell):
    events = [{"ev": "open", "t_sync": 10.0, "step": 20},
              {"ev": "close", "t_sync": 20.0, "step": 30}]
    spans = [{"name": "trainer:step_metrics", "t_mono": t,
              "attrs": {"step": 1, "resmix_sinkhorn_err": err}}
             for t, err in ((5.0, 1.0), (12.0, 2e-6), (18.0, 3e-6))] + [
        {"name": "trainer:step_metrics", "t_mono": 15.0,
         "attrs": {"step": 2, "moe_dropped": 0.0}}]
    err = cells.load_module("layer_metrics", "resmix.sinkhorn_err")
    monkeypatch.setattr(program, "setup_spans", lambda: spans)
    assert err.read(None, events, {}, cell) == 3e-6  # the window's largest
    monkeypatch.setattr(program, "setup_spans", lambda: spans[-1:])
    assert err.read(None, events, {}, cell) is None  # one residual lane
    monkeypatch.setattr(program, "setup_spans", lambda: [])
    assert err.read(None, events, {}, cell) is None
    assert err.read(None, [], {}, cell) is None  # no window at all


def _nano(cfg: dict, **over) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=4,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               kv_lora_rank=24, q_lora_rank=12, num_experts_per_tok=3,
               n_routed_experts=4, max_position_embeddings=64,
               hc_sinkhorn_iters=6, **over)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
    cfg["share"] = dict(cfg["share"], n_routed_experts_published=8,
                        first_expert=2)
    cfg["train"] = dict(cfg["train"], seq_len=64)
    cfg["program"] = dict(cfg["program"], dtype="float32",
                          use_flash_attention=False)
    return cfg


def test_the_seeded_leaves_draw_what_the_init_holds_constant(cell, mod):
    """`seeded_leaves`: Phi at the width that gives a logit's dynamic
    part its stated deviation, the biases off the init's with b_post's
    lanes and b_res's rows tilted, the embedding at its stated RMS, the q
    latent's down-projection scaled; every other leaf the init's; the
    same key the same leaves."""
    model = mod.build(_nano(cell["config"]))
    key = jax.random.PRNGKey(5)
    init = model.init_params(key, seq=64)
    drawn = mod.seeded_leaves(init, key)
    flat_init = dict(jax.tree_util.tree_leaves_with_path(init))
    flat = dict(jax.tree_util.tree_leaves_with_path(drawn))
    assert flat.keys() == flat_init.keys()
    touched = {jax.tree_util.keystr(path) for path in flat
               if not np.array_equal(flat[path], flat_init[path])}
    mixing = {f"['layers_{i}']['{sub}_hc']['{leaf}']" for i in range(2)
              for sub in ("attention", "feed_forward")
              for leaf in ("phi", "b_pre", "b_post", "b_res")}
    assert touched == mixing | {
        "['embed_tokens']['embedding']",
        "['layers_0']['attention']['q_a_proj']['kernel']",
        "['layers_1']['attention']['q_a_proj']['kernel']"}
    leaves = drawn["layers_1"]["feed_forward_hc"]
    n, d, _ = leaves["phi"].shape
    logit_std = float(leaves["phi"].std()) * 0.01 * math.sqrt(n * d)
    assert logit_std == pytest.approx(mod.SEEDED_LOGIT_STD, rel=0.05)
    tilts = np.mean([np.asarray(m[name]).reshape(n, -1).mean(-1)
                     - np.asarray(init["layers_0"]["attention_hc"][name]
                                  ).reshape(n, -1).mean(-1)
                     for layer in ("layers_0", "layers_1")
                     for m in (drawn[layer]["attention_hc"],
                               drawn[layer]["feed_forward_hc"])
                     for name in ("b_post", "b_res")], axis=0)
    np.testing.assert_allclose(
        tilts, mod.SEEDED_TILT * np.linspace(1, -1, n), atol=0.45)
    table = np.asarray(drawn["embed_tokens"]["embedding"])
    assert float(np.sqrt((table ** 2).mean())) == pytest.approx(
        mod.SEEDED_EMBEDDING_RMS, rel=0.05)
    again = mod.seeded_leaves(init, key)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(drawn), jax.tree.leaves(again)))


@pytest.mark.parametrize("mtp", [0, 1])
def test_reference_matches_program_at_nano_f32(cell, mod, mtp):
    cfg = _nano(cell["config"], num_nextn_predict_layers=mtp)
    model = mod.build(cfg)
    key = jax.random.PRNGKey(3)
    params = mod.seeded_leaves(model.init_params(key, seq=64), key)
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
    # each wrong-equation control moves the gradient's norm by over 1%
    # under the seeded leaves (under the init's own two of them move
    # nothing at all)
    for wrong in (dict(sinkhorn_iters=1), dict(post_factor=1.0),
                  dict(q_norm=False), dict(scale_mscale=False)):
        other = loss_and_grad_norm(mod.reference_loss(cfg, **wrong), params,
                                   batch, precision="highest")
        assert abs(other[1] - ref_norm) / ref_norm > 1e-2, wrong


def test_the_cells_control_flow_runs_on_the_cpu_at_nano_size(
        cell, monkeypatch, tmp_path):
    """The worker's own `train_process` — the seeded state with its
    drawn Phi and balanced selection biases, the check against the
    reference through the Trainer's compiled step, the window — on the
    CPU at a toy size.  Control flow only; no number of it means
    anything."""
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
    err = cells.load_module("layer_metrics", "resmix.sinkhorn_err")
    held = cells.load_module("layer_metrics", "moe.held_rows_share")
    # the seeded leaves, not the init's: six rounds leave a tilted
    # residual mix off by thousandths, the init's symmetric one by 1e-7
    assert 1e-5 < err.read(None, run["events"], {0: rec}, cell) < 1
    assert 0 < held.read(None, run["events"], {0: rec}, cell) < 100
