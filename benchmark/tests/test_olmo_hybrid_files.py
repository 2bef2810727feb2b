"""The Olmo-Hybrid-7B configuration: published widths and the five cuts,
what `build` refuses, operation counts against hand arithmetic, the
linear-attention readers on its scopes file, its plain reference against
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

CELL = "olmo_hybrid_7b.steady"
LINATTN = ("step.linattn_ms", "step.linattn_scan_ms",
           "kernel.delta_roofline", "linattn.padded_lanes_share")
REDUCED = ["num_hidden_layers", "layer_types", "linear_num_key_heads",
           "linear_num_value_heads", "vocab_size"]
# the catalog row Olmo-Hybrid-7B (model-configs guide), `config`
CATALOG = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": ["full_attention" if i % 4 == 3 else "linear_attention"
                    for i in range(32)],
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "olmo_hybrid")


def test_widths_are_the_catalog_rows_and_five_cuts_are_listed(cell, mod):
    cfg = cell["config"]
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED and entry["source"] == cfg["source"]
    for key, published in CATALOG.items():
        if key not in REDUCED:
            assert cfg[key] == published, key
    # the cuts: one whole period, half of a linear mixer's heads, an
    # eighth of the vocabulary
    assert cfg["layer_types"] == CATALOG["layer_types"][:4]
    assert cfg["layer_types"].count("full_attention") == 1
    assert cfg["num_hidden_layers"] == 4
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert cfg["linear_num_key_heads"] == cfg["linear_num_value_heads"] == 15
    share = cfg["share"]
    assert (share["linear_heads_published"], share["first_linear_head"],
            share["chips_sharing_a_linear_mixer"],
            share["vocabulary_slices"], share["pipeline_stages"],
            share["stage"], share["parameters"]) == \
        (30, 0, 2, 8, 8, 1, 795_736_986)
    for key in ("block", "qk_norm", "no_rotation", "gates", "l2_norms",
                "output_norm", "convolution", "initializer",
                "delta_chunk_size", "unused_keys"):
        assert cfg["assumed"][key], key
    assert "first of eight" in cfg["deployment"]
    assert cfg["num_params"]["total"] == 795_736_986
    assert "795,736,986" in cfg["num_params"]["sum"]
    rung = cfg["train"]["memory_rung"]
    assert rung["limit_GB"] == 14.4
    assert rung["live_GB"][next(k for k in rung["live_GB"]
                                if k.startswith(rung["taken"]))] < 14.4
    assert all(v for v in rung["live_GB"].values())  # both readings
    assert (cell["chips"], cell["global_batch"], cell["traffic_name"]) == \
        (1, 1, "steady")
    assert cell["seq_len"] == {"a": 8192, "b": 4096}[rung["taken"]]
    c = mod.build(cfg).config
    assert (c.hidden_size, c.intermediate_size, c.num_heads, c.num_kv_heads,
            c.linear_heads, c.linear_key_dim, c.linear_value_dim,
            c.conv_kernel, c.chunk_size, c.vocab_size, c.layer_types) == \
        (3840, 11008, 30, 30, 15, 96, 192, 4, 64, 12544,
         ("linear_attention",) * 3 + ("full_attention",))
    llama = c.attention_config()
    assert (llama.rope, llama.qk_norm, llama.head_dim) == (False, True, 128)
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == 795_736_986


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("rope_parameters", {"rope_theta": 500000.0}),
    ("linear_num_key_heads", 5), ("linear_allow_neg_eigval", False),
    ("num_hidden_layers", 3), ("hidden_act", "gelu"),
    ("num_attention_heads", 28), ("model_type", "granitemoehybrid"),
    ("layer_types", ["mamba"] * 4)])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, key, value):
    with pytest.raises(ValueError):
        mod.build(dict(cell["config"], **{key: value}))


def test_build_refuses_a_sequence_off_the_delta_rules_chunk(cell, mod):
    cfg = dict(cell["config"],
               train=dict(cell["config"]["train"], seq_len=8192 + 32))
    with pytest.raises(ValueError, match="chunk"):
        mod.build(cfg)


def test_operation_counts_against_hand_arithmetic(cell, mod):
    cfg = cell["config"]
    seq = cell["seq_len"]
    # per token, forward + backward, 6 FLOPs a matmul parameter passed
    linear = 6 * (3840 * (1440 + 1440 + 2880 + 2880 + 15 + 15)
                  + 2880 * 3840)                              # 265.8 MFLOP
    rule = 3 * 7 * 96 * 192 * 15                              # 5.8
    attn = 6 * 4 * 3840 * 3840                                # 353.9
    causal = 6 * seq * 3840                                   # 188.7
    mlp = 6 * 3 * 3840 * 11008                                # 760.9
    head = 6 * 3840 * 12544                                   # 289.0
    assert mod.train_flops_per_token(cfg) == \
        3 * (linear + rule) + attn + causal + 4 * mlp + head
    att = mod.attention_cost_per_step(cfg, 1)
    assert att["flops"] == 6 * 2 * 128 * (seq * (seq + 1) // 2) * 30
    assert att["bytes"] == 12 * 30 * seq * 128 * 2
    rec = mod.delta_cost_per_step(cfg, 1)
    assert rec["flops"] == 3 * 3 * seq * 7 * 96 * 192 * 15
    assert rec["bytes"] == 3 * 2 * seq * 15 * (2 * 96 + 2 * 192 + 2) * 2
    assert rec["flops_fwd"] * 3 == rec["flops"]
    # the bytes bound it: 1.04 ms against 0.72 ms of operations at 8192
    assert 0.65 < (rec["flops"] / 197e12) / (rec["bytes"] / 819e9) < 0.75


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    assert any(w["name"] == CELL for w in bench["workloads"])
    assert any(c["name"] == "olmo_hybrid_7b" for c in bench["configs"])
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(LINATTN)  # no expert or state-space metric
    for m in bench["per_layer"]:
        if m["name"] in LINATTN:
            assert CELL in m["workloads"]
    names = {m["name"] for m in cell["per_layer"]}
    assert set(LINATTN) <= names
    assert not names & {"step.collective_ms", "step.moe_experts_ms",
                        "step.ssm_ms", "kernel.ssd_roofline",
                        "attn.padded_lanes_share"}
    for m in cell["per_layer"]:  # every reader the cell asks for loads
        reader = cells.load_module("layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])
        assert reader.read(None, [], {}, cell) is None


def test_the_readers_split_the_step_by_the_scopes_file(monkeypatch, cell,
                                                       mod):
    rules = program.part_rules("olmo_hybrid")
    assert list(rules) == ["optimizer", "head_loss", "linattn", "mlp",
                           "attn_dense"]
    top = "OlmoHybrid/layers"
    la = f"{top}/linear_attention"
    table = {"fusion.1": f"fwd/{la}/q_proj", "fusion.2": f"fwd/{la}/conv",
             "fusion.3": f"bwd/{la}/delta", "fusion.4": f"bwd/{la}/gate_norm",
             "fusion.5": f"fwd/{top}/feed_forward/gate_proj",
             "fusion.6": f"bwd/{top}/feed_forward/down_proj",
             "fusion.7": f"fwd/{top}/attention/q_proj",
             "fusion.8": f"fwd/{top}/post_mixer_norm",
             "fusion.9": "fwd/OlmoHybrid/head",
             "fusion.10": "bwd/loss", "fusion.11": "optimizer",
             "fusion.12": f"fwd/{top}/attention/qk_norm",
             "fusion.13": f"fwd/{la}/gates"}
    durs = {"fusion.1": 3e6, "fusion.2": 5e6, "fusion.3": 70e6,
            "fusion.4": 11e6, "fusion.5": 13e6, "fusion.6": 17e6,
            "fusion.7": 19e6, "fusion.8": 23e6, "fusion.9": 29e6,
            "fusion.10": 31e6, "fusion.11": 37e6, "fusion.12": 41e6,
            "fusion.13": 2e6}
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

    assert read("step.linattn_ms") == 3.0 + 5.0 + 70.0 + 11.0 + 2.0
    assert read("step.linattn_scan_ms") == 5.0 + 70.0
    assert read("step.mlp_ms") == 13.0 + 17.0
    assert read("step.attn_dense_ms") == 19.0
    assert read("step.head_loss_ms") == 29.0 + 31.0
    assert read("step.optimizer_ms") == 37.0
    assert read("step.unscoped_ms") == 23.0 + 41.0  # norms, the QK-norm
    share = read("kernel.delta_roofline")
    cost = mod.delta_cost_per_step(cell["config"], cell["global_batch"])
    assert share == pytest.approx(100 * cost["bytes"] / 819e9 * 1e3 / 75.0)
    assert 0 < share < 100


def test_the_lanes_reader_reads_the_programs_counters(monkeypatch, cell):
    events = [{"ev": "open", "t": 1.0, "t_sync": 1.0, "gen": 0, "step": 10},
              {"ev": "close", "t": 9.0, "t_sync": 9.0, "gen": 0, "step": 20}]
    spans = [{"name": "trainer:step_metrics", "t_mono": t, "dur_s": 0.0,
              "attrs": {"step": 10, "delta_lanes_run": run,
                        "delta_lanes_model": 864.0}}
             for t, run in ((0.5, 1.0), (2.0, 864.0), (5.0, 960.0))]
    monkeypatch.setattr(program, "setup_spans", lambda: spans)
    reader = cells.load_module("layer_metrics", "linattn.padded_lanes_share")
    assert reader.read(None, events, {}, cell) == \
        pytest.approx(100 * (0.0 + 0.1) / 2)
    monkeypatch.setattr(program, "setup_spans", lambda: [])
    assert reader.read(None, events, {}, cell) is None


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, linear_num_key_heads=3,
               linear_num_value_heads=3, linear_key_head_dim=8,
               linear_value_head_dim=24, intermediate_size=96,
               layer_types=["linear_attention", "full_attention",
                            "linear_attention"],
               num_hidden_layers=3, max_position_embeddings=64)
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
    # float32 on both sides: only the order of sums differs
    assert abs(sys_loss - ref_loss) / ref_loss < 1e-5
    assert abs(sys_norm - ref_norm) / ref_norm < 1e-4
    # and each wrong equation is another number
    from benchmark import reference_olmo_hybrid

    for wrong in reference_olmo_hybrid.WRONG:
        off, _ = loss_and_grad_norm(mod.reference_loss(cfg, wrong=wrong),
                                    params, batch, precision="highest")
        assert abs(off - ref_loss) / ref_loss > 1e-4, wrong


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
    assert cells.load_module(
        "layer_metrics", "linattn.padded_lanes_share").read(
            None, run["events"], {0: rec}, cell) == 0.0
