"""The Phi-4-mini-flash-reasoning configuration: published widths and the
two cuts, what `build` refuses, operation counts against hand
arithmetic, the readers on its scopes file, its plain reference against
the program at a tiny size on the CPU (both float32), each wrong
equation and the precision control told from it, and the cell's control
flow rehearsed on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, program, reference_phi4flash, worker
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

CELL = "phi4_mini_flash.steady"
JOINED = ("step.ssm_ms", "step.ssm_scan_ms", "kernel.attn_window_ms",
          "kernel.attn_window_roofline", "attn.window_tiles_share",
          "attn.padded_lanes_share")
NEW = ("kernel.sscan_roofline", "step.gmu_ms", "step.attn_diff_ms",
       "attn.diff_lambda_mean", "gmu.gate_mean")
REDUCED = ["num_hidden_layers", "vocab_size"]
# the catalog row Phi-4-mini-flash-reasoning (model-configs guide), `config`
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "phi4flash")


def test_widths_are_the_catalog_rows_and_two_cuts_are_listed(cell, mod):
    cfg = cell["config"]
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED and entry["source"] == cfg["source"]
    for key, published in CATALOG.items():
        if key not in REDUCED:
            assert cfg[key] == published, key
    share = cfg["share"]
    assert share["layer_ids"] == [0, 1, 16, 17, 18, 19]
    assert cfg["num_hidden_layers"] == 6 >= 4
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert (share["vocab_size_published"], share["vocabulary_slices"],
            share["num_hidden_layers_published"]) == (200064, 8, 32)
    assert cfg["assumed"]["mamba"] == {"d_state": 16, "d_conv": 4,
                                       "expand": 2, "dt_rank": 160}
    for key in ("mamba_reason", "initializer", "biases", "layer_norm",
                "head_pairing", "lambda_init", "sub_norm", "no_position",
                "swiglu", "gmu", "unused_keys"):
        assert cfg["assumed"][key], key
    assert cfg["num_params"]["total"] == 697_094_272
    assert "697,094,272" in cfg["num_params"]["sum"]
    rung = cfg["train"]["memory_rung"]
    assert rung["limit_GB"] == 14.4
    assert 4.0 < rung["live_GB"][rung["taken"]] <= 14.4
    assert len(rung["live_GB"]) == 2  # both rungs' readings
    assert (cell["chips"], cell["global_batch"], cell["traffic_name"]) == \
        (1, 1, "steady")
    assert str(cell["seq_len"]) in rung["taken"].replace(",", "")
    c = mod.build(cfg).config
    assert (c.hidden_size, c.intermediate_size, c.num_heads, c.num_kv_heads,
            c.head_dim, c.sliding_window, c.mb_per_layer, c.vocab_size,
            c.layers, c.norm_eps) == \
        (2560, 10240, 40, 20, 64, 512, 2, 25008, (0, 1, 16, 17, 18, 19),
         1e-5)
    m = c.mamba_config()
    assert (m.d_inner, m.state_size, m.conv_kernel, m.rank) == \
        (5120, 16, 4, 160)
    assert [c.kind(i) for i in c.layers] == \
        ["mamba", "window", "mamba", "full", "gmu", "cross"]
    assert [round(c.lambda_init(i), 4) for i in (1, 17, 19)] == \
        [0.3555, 0.7963, 0.798]
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == 697_094_272
    # the uncut model is the published 3.8B
    whole = mod.build({**cfg, "num_hidden_layers": 32, "vocab_size": 200064,
                       "share": {**share, "layer_ids": list(range(32))}})
    assert whole.config.num_params() == 3_852_562_944


@pytest.mark.parametrize("key,value", [
    ("model_type", "phi3"), ("hidden_act", "gelu"), ("mlp_bias", True),
    ("lm_head_bias", True), ("tie_word_embeddings", False),
    ("resid_pdrop", 0.1), ("num_key_value_heads", 5),
    ("num_hidden_layers", 5), ("max_position_embeddings", 4096)])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, key, value):
    with pytest.raises(ValueError):
        mod.build(dict(cell["config"], **{key: value}))


@pytest.mark.parametrize("ids", [[0, 1, 16, 18, 19, 20], [0, 1, 2, 3, 17, 18],
                                 [1, 0, 16, 17, 18, 19]])
def test_build_refuses_a_cut_whose_reader_comes_without_its_source(
        cell, mod, ids):
    cfg = dict(cell["config"], share=dict(cell["config"]["share"],
                                          layer_ids=ids))
    with pytest.raises(ValueError):
        mod.build(cfg)


def test_operation_counts_against_hand_arithmetic(cell, mod):
    cfg = cell["config"]
    t = cell["seq_len"]
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    self_attn = 2560 * 5120 + 2560 * 2560
    cross = 2 * 2560 * 2560
    gmu = 2 * 2560 * 5120
    mlp = 3 * 2560 * 10240
    head = 2560 * 25008
    assert mod.dense_params_per_token(cfg) == {
        "mamba": 2 * mamba, "gmu": gmu, "attention": 2 * self_attn + cross,
        "mlp": 6 * mlp, "head": head}
    causal, band = t * (t + 1) // 2, 512 * t - 512 * 511 // 2
    assert mod.kept_pairs(t) == causal and mod.kept_pairs(t, 512) == band
    pairs = 40 * (band + 2 * causal)
    scan = 7 * 5120 * 16
    assert mod.train_flops_per_token(cfg) == 6.0 * (
        2 * mamba + gmu + 2 * self_attn + cross + 6 * mlp + head) \
        + 3.0 * (2 * 64 + 2 * 128) * pairs / t + 3.0 * 2 * scan
    att = mod.attention_cost_per_step(cfg, 1)
    assert att["flops_fwd"] == pairs * (2 * 64 + 2 * 128)
    assert att["flops_bwd"] == 2 * att["flops_fwd"]
    # q, dq at 64, o, dO at 128 a query head; k, v and theirs at 20 x 64
    assert att["bytes"] == 3 * 2 * t * (
        3 * 40 * 64 + 3 * 40 * 128 + 6 * 20 * 64)
    win = mod.window_attention_cost_per_step(cfg, 1)
    assert win["flops"] == 3 * 40 * band * (2 * 64 + 2 * 128)
    assert win["bytes"] * 3 == att["bytes"]
    sscan = mod.sscan_cost_per_step(cfg, 1)
    assert sscan["flops"] == 2 * 3 * t * scan
    assert sscan["bytes"] == 2 * 2 * t * (3 * 5120 + 2 * 16) * 2
    # the bytes bound it (2.46 ms): the operations are 0.29 ms at the
    # matrix unit's peak, which no vector unit reaches
    assert 8 < (sscan["bytes"] / 819e9) / (sscan["flops"] / 197e12) < 9


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "phi4_mini_flash"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(JOINED) | set(NEW)
    for m in bench["per_layer"]:
        if m["name"] in JOINED:
            assert m["workloads"][-1] == CELL
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == list(NEW)
    names = {m["name"] for m in cell["per_layer"]}
    assert not names & {"kernel.ssd_roofline", "step.collective_ms",
                        "step.moe_experts_ms", "moe.load_max_over_mean"}
    for m in cell["per_layer"]:  # every reader the cell asks for loads
        reader = cells.load_module("layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])
        if m["name"] in NEW:  # nothing to read: nothing said
            assert reader.read(None, [], {}, cell) is None


def test_the_readers_split_the_step_by_the_scopes_file(monkeypatch, cell,
                                                       mod):
    rules = program.part_rules("phi4flash")
    assert list(rules) == ["optimizer", "head_loss", "ssm", "gmu", "mlp",
                           "attn_dense"]
    top = "Phi4Flash/layers"
    table = {"fusion.1": f"fwd/{top}/mamba/in_proj",
             "fusion.2": f"fwd/{top}/mamba/conv",
             "dwt_sscan_bwd.1": f"bwd/{top}/mamba/sscan",
             "fusion.4": f"bwd/{top}/mamba/dt_proj",
             "fusion.5": f"fwd/{top}/feed_forward/gate_proj",
             "fusion.6": f"bwd/{top}/gmu/out_proj",
             "fusion.7": f"fwd/{top}/attention/qkv_proj",
             "fusion.8": f"fwd/{top}/attention/diff",
             "fusion.9": "fwd/Phi4Flash/head",
             "fusion.10": "bwd/loss", "fusion.11": "optimizer",
             "fusion.12": f"fwd/{top}/input_norm",
             "fusion.13": f"bwd/{top}/attention/q_proj"}
    durs = {"fusion.1": 3e6, "fusion.2": 5e6, "dwt_sscan_bwd.1": 7e6,
            "fusion.4": 11e6, "fusion.5": 13e6, "fusion.6": 17e6,
            "fusion.7": 19e6, "fusion.8": 23e6, "fusion.9": 29e6,
            "fusion.10": 31e6, "fusion.11": 37e6, "fusion.12": 41e6,
            "fusion.13": 43e6}
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

    assert read("step.ssm_ms") == 3.0 + 5.0 + 7.0 + 11.0
    assert read("step.ssm_scan_ms") == 5.0 + 7.0
    assert read("step.gmu_ms") == 17.0
    assert read("step.attn_diff_ms") == 23.0
    assert read("step.mlp_ms") == 13.0
    assert read("step.attn_dense_ms") == 19.0 + 43.0
    assert read("step.head_loss_ms") == 29.0 + 31.0
    assert read("step.unscoped_ms") == 23.0 + 41.0  # diff, the norms
    share = read("kernel.sscan_roofline")
    cost = mod.sscan_cost_per_step(cell["config"], cell["global_batch"])
    assert share == pytest.approx(100 * cost["bytes"] / 819e9 * 1e3 / 12.0)
    assert read("kernel.ssd_roofline") is None  # not the dual form's count


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=96,
               sliding_window=8, max_position_embeddings=64)
    cfg["share"].update(num_hidden_layers_published=8,
                        layer_ids=[0, 1, 4, 5, 6, 7])
    cfg["assumed"]["mamba"].update(dt_rank=4)
    cfg["train"] = dict(cfg["train"], seq_len=32)
    cfg["program"] = dict(cfg["program"], dtype="float32")
    return cfg


@pytest.fixture(scope="module")
def nano(cell, mod):
    cfg = _nano(cell["config"])
    model = mod.build(cfg)
    assert [model.config.kind(i) for i in model.config.layers] == \
        ["mamba", "window", "mamba", "full", "gmu", "cross"]
    params = model.init_params(jax.random.PRNGKey(3))
    batch = {k: jnp.asarray(v) for k, v in
             make_data(256, 4, 32, seed=3)(0).items()}
    from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

    return cfg, params, batch, loss_and_grad_norm(
        make_lm_loss(model.apply), params, batch, precision="highest")


def test_reference_matches_program_at_nano_f32(mod, nano):
    cfg, params, batch, (sys_loss, sys_norm) = nano
    ref_loss, ref_norm = loss_and_grad_norm(
        mod.reference_loss(cfg), params, batch, precision="highest")
    # float32 on both sides: only the order of sums differs
    assert abs(sys_loss - ref_loss) / ref_loss < 1e-5
    assert abs(sys_norm - ref_norm) / ref_norm < 1e-4


@pytest.mark.parametrize("control", [
    {"wrong": (w,)} for w in reference_phi4flash.WRONG]
    + [{"dtype": jnp.bfloat16}], ids=lambda c: str(*c.values()))
def test_a_wrong_equation_or_a_lower_precision_is_told_apart(mod, nano,
                                                             control):
    cfg, params, batch, (sys_loss, sys_norm) = nano
    ref_loss, ref_norm = loss_and_grad_norm(
        mod.reference_loss(cfg, **control), params, batch,
        precision="highest")
    assert max(abs(sys_loss - ref_loss) / ref_loss,
               abs(sys_norm - ref_norm) / ref_norm) > 1e-3


def test_the_cells_control_flow_runs_on_the_cpu_at_nano_size(
        cell, monkeypatch, tmp_path):
    """The worker's own `train_process` — the seeded state, the check
    against the reference through the Trainer's compiled step, the
    window — on the CPU at a toy size.  Control flow only; no number of
    it means anything."""
    from benchmark.drivers import trainer_inproc

    cell = dict(cell, config=_nano(cell["config"]), seq_len=32,
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
    # the counters the new per-layer metrics read ride the step's metrics
    for name, lo, hi in (("attn.diff_lambda_mean", 0.3, 1.0),
                         ("gmu.gate_mean", -0.3, 0.6),
                         ("attn.window_tiles_share", 0.0, 100.0),
                         ("attn.padded_lanes_share", 0.0, 0.0)):
        got = cells.load_module("layer_metrics", name).read(
            None, run["events"], {0: rec}, cell)
        assert got is not None and lo <= got <= hi, (name, got)
