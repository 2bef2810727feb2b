"""The SmallThinker-21B-A3B configuration: published widths and the
three cuts, what `build` refuses, operation counts against hand
arithmetic (KEPT pairs, never the causal triangle for a windowed layer),
the readers on its scopes file and counters, its plain reference against
the program at a tiny size on the CPU (both float32), and the cell's
control flow rehearsed on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, program, worker
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

CELL = "smallthinker_21b_a3b.steady"
NEW = ("kernel.attn_window_ms", "kernel.attn_window_roofline",
       "attn.window_tiles_share")
JOINED = ("step.moe_experts_ms", "step.moe_route_ms",
          "kernel.moe_gmm_roofline", "moe.load_max_over_mean",
          "moe.held_rows_share")
REDUCED = ["num_hidden_layers", "rope_layout", "sliding_window_layout",
           "moe_num_primary_experts", "vocab_size"]
# the catalog row SmallThinker-21BA3B-Instruct (model-configs guide)
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "smallthinker")


def test_widths_are_the_catalog_rows_and_three_cuts_are_listed(cell, mod):
    cfg = cell["config"]
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED and entry["source"] == cfg["source"]
    for key, published in CATALOG.items():
        if key not in REDUCED:
            assert cfg[key] == published, key
    # the cuts, each at the guide's floor or above: one whole period, 16
    # of 64 experts held, an eighth of the vocabulary
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] \
        == CATALOG["rope_layout"][:4] == [0, 1, 1, 1]
    assert cfg["num_hidden_layers"] == 4
    assert cfg["moe_num_primary_experts"] == 16
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    share = cfg["share"]
    assert (share["moe_num_primary_experts_published"],
            share["first_expert"], share["chips_sharing_a_layer"],
            share["vocab_size_published"], share["vocabulary_slices"],
            share["num_hidden_layers_published"],
            share["pipeline_stages"]) == (64, 0, 4, 151936, 8, 52, 13)
    assert share["parameters"] == 559_290_880
    assert "559,290,880" in share["parameters_sum"]
    for key in ("router_aux_loss_coef_origin", "primary_experts_only",
                "expert_form", "router", "attention", "initializer",
                "unused_keys"):
        assert cfg["assumed"][key], key
    assert "first of thirteen" in cfg["deployment"]
    assert "QUARTER" in cfg["deployment"] or "HALF" in cfg["deployment"]
    assert (cell["chips"], cell["seq_len"], cell["traffic_name"]) == \
        (1, 16384, "steady")
    assert cell["global_batch"] in (1, 2)
    c = mod.build(cfg).config
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            c.expert_width, c.num_experts, c.top_k, c.experts_held,
            c.first_expert, c.vocab_size, c.sliding_window_size,
            c.rope_layout, c.sliding_window_layout) == \
        (2560, 28, 4, 128, 768, 64, 6, 16, 0, 18992, 4096, (0, 1, 1, 1),
         (0, 1, 1, 1))
    glob, local = c.attention_config(0), c.attention_config(1)
    assert (glob.rope, glob.attn_window) == (False, 0)
    assert (local.rope, local.attn_window, local.rope_theta) == \
        (True, 4096, 1.5e6)
    moe = c.moe_config()
    assert (moe.expert_act, moe.score_func, moe.norm_topk_prob, moe.impl,
            moe.aux_loss, moe.held) == \
        ("reglu", "softmax", True, "grouped", "topk", 16)
    # the file's ASSUMED load-balancing term, the mean over the layers
    assert cfg["assumed"]["router_aux_loss_coef"] == 0.01
    assert moe.aux_loss_weight == 0.01 / 4
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == 559_290_880


@pytest.mark.parametrize("key,value", [
    ("model_name", "olmoe"), ("moe_primary_router_apply_softmax", False),
    ("norm_topk_prob", False), ("rope_scaling", {"type": "yarn"}),
    ("tie_word_embeddings", True), ("rope_layout", [0, 1, 1]),
    ("sliding_window_layout", [0, 1, 1, 2]), ("num_key_value_heads", 5),
    ("num_hidden_layers", 5)])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, key, value):
    with pytest.raises(ValueError):
        mod.build(dict(cell["config"], **{key: value}))


def test_operation_counts_against_hand_arithmetic(cell, mod):
    cfg = cell["config"]
    seq, win = 16384, 4096
    causal = seq * (seq + 1) // 2
    band = win * seq - win * (win - 1) // 2
    assert (mod.kept_pairs(seq, None), mod.kept_pairs(seq, win)) == \
        (causal, band)
    # brute force at a small size; a window no shorter than the sequence
    # keeps the triangle
    assert mod.kept_pairs(40, 7) == sum(min(i + 1, 7) for i in range(40))
    assert mod.kept_pairs(40, 40) == mod.kept_pairs(40, 99) == 40 * 41 // 2
    assert 0.43 < band / causal < 0.44  # the issue's 44%
    # per token, forward + backward, 6 FLOPs a matmul parameter passed
    attn = 6 * (2 * 2560 * 3584 + 2 * 2560 * 512)            # 125.8 MFLOP
    router = 6 * 2560 * 64
    experts = 6 * 1.5 * 3 * 2560 * 768        # 6 x 16 / 64 rows a token
    head = 6 * 2560 * 18992
    pairs = 12 * 3584 * (causal + 3 * band) / seq
    assert mod.train_flops_per_token(cfg) == pytest.approx(
        4 * (attn + router + experts) + head + pairs, rel=1e-12)
    att = mod.attention_cost_per_step(cfg, 1)
    assert att["flops"] == 6 * 2 * 128 * (causal + 3 * band) * 28
    # q, o, dO, dq once a query head; k, v, dk, dv once a key/value head
    assert att["bytes"] == 4 * 6 * (28 + 4) * seq * 128 * 2
    local = mod.window_attention_cost_per_step(cfg, 2)
    assert local["flops"] == 2 * 6 * 2 * 128 * 3 * band * 28
    assert local["bytes"] == 2 * 3 * 6 * (28 + 4) * seq * 128 * 2
    assert local["flops_fwd"] * 3 == local["flops"]
    # compute-bound: 77 ms of operations against 6 ms of bytes (2 sequences)
    assert 12 < (local["flops"] / 197e12) / (local["bytes"] / 819e9) < 14
    moe = mod.moe_cost_per_step(cfg, 1)
    rows = int(seq * 1.5)
    assert moe["flops"] == 4 * 9 * 2 * rows * 2560 * 768
    assert moe["bytes"] == 4 * (5 * rows * 2560 * 2
                                + 3 * 3 * 16 * 2560 * 768 * 2)


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    assert CELL in [w["name"] for w in bench["workloads"]]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("smallthinker_21b_a3b", "steady", 1)
    assert len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | set(JOINED)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"] and m["moves"] == "tokens_per_s"
            assert m["layer"] == "kernels"
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | set(JOINED) <= names
    assert not names & {"step.collective_ms", "step.ssm_ms",
                        "step.ssm_scan_ms", "kernel.ssd_roofline"}
    for m in cell["per_layer"]:  # every reader the cell asks for loads
        reader = cells.load_module("layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])


def test_the_readers_split_the_step_by_the_scopes_file(monkeypatch, cell,
                                                       mod):
    rules = program.part_rules("smallthinker")
    assert list(rules) == ["optimizer", "head_loss", "mlp", "attn_dense"]
    top = "SmallThinker/layers"
    table = {"fusion.1": f"fwd/{top}/feed_forward/moe/router",
             "fusion.2": f"fwd/{top}/feed_forward/moe/dispatch",
             "dwt_gmm.3": f"bwd/{top}/feed_forward/moe/experts/dwt_gmm",
             "fusion.4": f"bwd/{top}/feed_forward/moe/combine",
             "fusion.5": f"fwd/{top}/attention/q_proj",
             "fusion.6": f"bwd/{top}/attention/o_proj",
             "fusion.7": f"fwd/{top}/attention",
             "fusion.8": f"fwd/{top}/input_norm",
             "fusion.9": "fwd/SmallThinker/head",
             "fusion.10": "bwd/loss", "fusion.11": "optimizer"}
    durs = {"fusion.1": 3e6, "fusion.2": 5e6, "dwt_gmm.3": 7e6,
            "fusion.4": 11e6, "fusion.5": 13e6, "fusion.6": 17e6,
            "fusion.7": 19e6, "fusion.8": 23e6, "fusion.9": 29e6,
            "fusion.10": 31e6, "fusion.11": 37e6,
            "dwt_fa_win_fwd.1": 40e6, "dwt_fa_win_bwd_dq.1": 60e6,
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

    assert read("step.mlp_ms") == 3.0 + 5.0 + 7.0 + 11.0
    assert read("step.moe_experts_ms") == 7.0
    assert read("step.moe_route_ms") == 3.0 + 5.0 + 11.0  # the router's too
    assert read("step.attn_dense_ms") == 13.0 + 17.0
    assert read("step.head_loss_ms") == 29.0 + 31.0
    assert read("step.optimizer_ms") == 37.0
    assert read("step.unscoped_ms") == 19.0 + 23.0  # RoPE / repeat, norms
    assert read("kernel.attn_ms") == 150.0
    assert read("kernel.attn_window_ms") == 100.0
    cost = mod.window_attention_cost_per_step(cell["config"],
                                              cell["global_batch"])
    assert read("kernel.attn_window_roofline") == pytest.approx(
        100 * cost["flops"] / 197e12 * 1e3 / 100.0)
    # a trace without windowed kernels: both leave their metric out
    trace["devices"]["0"]["ops"] = [o for o in ops if "win" not in o[0]]
    assert read("kernel.attn_window_ms") is None
    assert read("kernel.attn_window_roofline") is None
    assert read("attn.window_tiles_share") is None  # no window, no events


def test_the_tiles_share_reads_the_steps_counters(monkeypatch, cell):
    events = [{"ev": "open", "t_sync": 10.0, "step": 20},
              {"ev": "close", "t_sync": 20.0, "step": 30}]
    spans = [{"name": "trainer:step_metrics", "t_mono": t,
              "attrs": {"step": 1, "attn_tiles_window": 252.0 * 84,
                        "attn_tiles_causal": 528.0 * 84}}
             for t in (5.0, 12.0, 18.0)] + [
        {"name": "trainer:step_metrics", "t_mono": 15.0,
         "attrs": {"step": 2, "moe_dropped": 0.0}}]
    monkeypatch.setattr(program, "setup_spans", lambda: spans)
    share = cells.load_module("layer_metrics", "attn.window_tiles_share")
    assert share.read(None, events, {}, cell) == pytest.approx(
        100 * 252 / 528)
    monkeypatch.setattr(program, "setup_spans", lambda: spans[-1:])
    assert share.read(None, events, {}, cell) is None


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, moe_ffn_hidden_size=32,
               moe_num_active_primary_experts=3, moe_num_primary_experts=4,
               sliding_window_size=24, max_position_embeddings=64)
    cfg["share"] = dict(cfg["share"], moe_num_primary_experts_published=8,
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
    # the wrong-equation control: the window dropped from the mask
    no_window = loss_and_grad_norm(
        mod.reference_loss(cfg, sliding_window_layout=(0, 0, 0, 0)),
        params, batch, precision="highest")
    assert abs(no_window[1] - ref_norm) / ref_norm > 1e-3


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
    assert cells.load_module("end_to_end", "tokens_per_s").read(
        None, run["events"], {0: rec}, cell) > 0
    share = cells.load_module("layer_metrics", "attn.window_tiles_share")
    held = cells.load_module("layer_metrics", "moe.held_rows_share")
    assert 0 < share.read(None, run["events"], {0: rec}, cell) <= 100
    assert 0 < held.read(None, run["events"], {0: rec}, cell) < 100
