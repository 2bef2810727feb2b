"""The granite-4.0-h-micro configuration: published widths and the three
cuts, what `build` refuses, operation counts against hand arithmetic,
the state-space readers on its scopes file, its plain reference against
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

CELL = "granite4_h_micro.steady"
SSM = ("step.ssm_ms", "step.ssm_scan_ms", "kernel.ssd_roofline")
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
# the catalog row granite-4.0-h-micro (model-configs guide), `config`
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": ["attention" if i % 10 == 5 else "mamba"
                    for i in range(40)],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "granite_hybrid")


def test_widths_are_the_catalog_rows_and_three_cuts_are_listed(cell, mod):
    cfg = cell["config"]
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED and entry["source"] == cfg["source"]
    for key, published in CATALOG.items():
        if key not in REDUCED:
            assert cfg[key] == published, key
    # the cuts, each at the guide's floor: one whole period, an eighth of
    # the vocabulary
    assert cfg["layer_types"] == CATALOG["layer_types"][:10]
    assert cfg["layer_types"].count("attention") == 1
    assert cfg["num_hidden_layers"] == 10
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    share = cfg["share"]
    assert (share["vocab_size_published"], share["vocabulary_slices"],
            share["num_hidden_layers_published"],
            share["pipeline_stages"]) == (100352, 8, 40, 4)
    for key in ("initializer", "time_step", "mamba_chunk_size", "d_inner",
                "no_auxiliary_loss", "gate_norm", "unused_keys"):
        assert cfg["assumed"][key], key
    assert "first of four" in cfg["deployment"]
    assert cfg["num_params"]["total"] == 772_160_448
    assert "772,160,448" in cfg["num_params"]["sum"]
    rung = cfg["train"]["memory_rung"]
    assert rung["taken"] == "a" and rung["limit_GB"] == 14.4
    assert rung["live_GB"]["a: 1 x 8192, chunk 256"] < 14.4
    assert (cell["chips"], cell["seq_len"], cell["global_batch"],
            cell["traffic_name"]) == (1, 8192, 1, "steady")
    c = mod.build(cfg).config
    assert (c.hidden_size, c.intermediate_size, c.num_heads, c.num_kv_heads,
            c.mamba_heads, c.mamba_head_dim, c.n_groups, c.state_size,
            c.conv_kernel, c.chunk_size, c.vocab_size, c.layer_types) == \
        (2048, 8192, 32, 8, 64, 64, 1, 128, 4, 256, 12544,
         ("mamba",) * 5 + ("attention",) + ("mamba",) * 4)
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.attention_multiplier, c.logits_scaling) == \
        (12, 0.22, 0.015625, 8)
    llama = c.attention_config()
    assert (llama.rope, llama.attn_scale, llama.head_dim) == \
        (False, 0.015625, 64)
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == 772_160_448


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("num_experts_per_tok", 2),
    ("attention_bias", True), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("position_embedding_type", "rope"),
    ("mamba_n_groups", 3), ("tie_word_embeddings", False),
    ("num_hidden_layers", 9), ("hidden_act", "gelu"),
    ("shared_intermediate_size", 4096), ("mamba_expand", 3),
    ("model_type", "nemotron_h")])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, key, value):
    with pytest.raises(ValueError):
        mod.build(dict(cell["config"], **{key: value}))


def test_build_refuses_a_sequence_off_the_scans_chunk(cell, mod):
    cfg = dict(cell["config"],
               train=dict(cell["config"]["train"], seq_len=8192 + 128))
    with pytest.raises(ValueError, match="chunk"):
        mod.build(cfg)


def test_operation_counts_against_hand_arithmetic(cell, mod):
    cfg = cell["config"]
    # per token, forward + backward, 6 FLOPs a matmul parameter passed
    mamba = 6 * (2048 * (4096 + 4352 + 64) + 4096 * 2048)    # 154.9 MFLOP
    scan = 3 * 6 * 64 * 128 * 64                             # 9.4
    attn = 6 * (2 * 2048 * 2048 + 2 * 2048 * 512)            # 62.9
    causal = 6 * 8192 * 2048                                 # 100.7
    mlp = 6 * 3 * 2048 * 8192                                # 302.0
    head = 6 * 2048 * 12544                                  # 154.1
    assert mod.train_flops_per_token(cfg) == \
        9 * (mamba + scan) + attn + causal + 10 * mlp + head
    att = mod.attention_cost_per_step(cfg, 1)
    kept = 8192 * 8193 // 2
    assert att["flops"] == 6 * 2 * 64 * kept * 32
    # q, o, dO, dq once a query head; k, v, dk, dv once a key/value head
    assert att["bytes"] == 6 * (32 + 8) * 8192 * 64 * 2
    ssd = mod.ssd_cost_per_step(cfg, 1)
    assert ssd["flops"] == 9 * 3 * 8192 * 6 * 64 * 128 * 64
    assert ssd["bytes"] == 9 * 2 * 8192 * (2 * 4096 + 2 * 128 + 64) * 2
    assert ssd["flops_fwd"] * 3 == ssd["flops"]
    # one group: B and C are an eighth of the other hybrid's, so the
    # operations bound (3.53 ms) passes the bytes (3.05 ms)
    assert 1.1 < (ssd["flops"] / 197e12) / (ssd["bytes"] / 819e9) < 1.2


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "granite4_h_micro"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(SSM)  # no expert metric lists this cell
    for m in bench["per_layer"]:
        if m["name"] in SSM:
            assert m["workloads"] == ["nemotron3_nano_30b_a3b.steady", CELL]
    names = {m["name"] for m in cell["per_layer"]}
    assert set(SSM) <= names
    assert not names & {"step.collective_ms", "step.moe_experts_ms",
                        "step.moe_route_ms", "kernel.moe_gmm_roofline",
                        "moe.load_max_over_mean", "moe.held_rows_share"}
    for m in cell["per_layer"]:  # every reader the cell asks for loads
        reader = cells.load_module("layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])


def test_the_readers_split_the_step_by_the_scopes_file(monkeypatch, cell,
                                                       mod):
    rules = program.part_rules("granite_hybrid")
    assert list(rules) == ["optimizer", "head_loss", "ssm", "mlp",
                           "attn_dense"]
    top = "GraniteHybrid/layers"
    table = {"fusion.1": f"fwd/{top}/mamba/in_proj",
             "fusion.2": f"fwd/{top}/mamba/conv",
             "fusion.3": f"bwd/{top}/mamba/ssd",
             "fusion.4": f"bwd/{top}/mamba/gate_norm",
             "fusion.5": f"fwd/{top}/feed_forward/gate_proj",
             "fusion.6": f"bwd/{top}/feed_forward/down_proj",
             "fusion.7": f"fwd/{top}/attention/q_proj",
             "fusion.8": f"fwd/{top}/input_norm",
             "fusion.9": "fwd/GraniteHybrid/head",
             "fusion.10": "bwd/loss", "fusion.11": "optimizer",
             "fusion.12": f"fwd/{top}/attention"}
    durs = {"fusion.1": 3e6, "fusion.2": 5e6, "fusion.3": 70e6,
            "fusion.4": 11e6, "fusion.5": 13e6, "fusion.6": 17e6,
            "fusion.7": 19e6, "fusion.8": 23e6, "fusion.9": 29e6,
            "fusion.10": 31e6, "fusion.11": 37e6, "fusion.12": 41e6}
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

    assert read("step.ssm_ms") == 3.0 + 5.0 + 70.0 + 11.0
    assert read("step.ssm_scan_ms") == 5.0 + 70.0
    assert read("step.mlp_ms") == 13.0 + 17.0
    assert read("step.attn_dense_ms") == 19.0
    assert read("step.head_loss_ms") == 29.0 + 31.0
    assert read("step.optimizer_ms") == 37.0
    assert read("step.unscoped_ms") == 23.0 + 41.0  # norms, GQA's repeat
    # 3.53 ms of operations at the published peak over 75 ms
    share = read("kernel.ssd_roofline")
    cost = mod.ssd_cost_per_step(cell["config"], cell["global_batch"])
    assert share == pytest.approx(100 * cost["flops"] / 197e12 * 1e3 / 75.0)
    assert 4.5 < share < 5.0


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
               mamba_d_state=16, intermediate_size=96,
               shared_intermediate_size=96,
               layer_types=["mamba", "attention", "mamba"],
               num_hidden_layers=3, max_position_embeddings=64)
    cfg["train"] = dict(cfg["train"], seq_len=64)
    cfg["program"] = dict(cfg["program"], dtype="float32",
                          use_flash_attention=False, mamba_chunk_size=16)
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
