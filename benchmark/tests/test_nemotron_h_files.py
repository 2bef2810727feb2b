"""The Nemotron-3-Nano configuration: published widths and the three
cuts, operation counts against hand arithmetic, the four readers it
brings, its plain reference against the program at a tiny size on the
CPU (both float32), and the cell's control flow rehearsed on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, program, worker
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

CELL = "nemotron3_nano_30b_a3b.steady"
NEW = ("step.ssm_ms", "step.ssm_scan_ms", "kernel.ssd_roofline",
       "moe.held_rows_share")
LISTED = ("step.moe_experts_ms", "step.moe_route_ms",
          "kernel.moe_gmm_roofline", "moe.load_max_over_mean")
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]
# the catalog row NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (model-configs
# guide), `config`
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def test_widths_are_the_catalog_rows_and_three_cuts_are_listed(cell):
    cfg = cell["config"]
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED and entry["source"] == cfg["source"]
    for key, published in CATALOG.items():
        if key not in REDUCED:
            assert cfg[key] == published, key
    # the cuts, each inside the guide's floors: one whole period, 8
    # routed experts, an eighth of the vocabulary
    assert cfg["hybrid_override_pattern"] == \
        CATALOG["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert cfg["num_hidden_layers"] == 9
    assert cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    share = cfg["share"]
    assert (share["n_routed_experts_published"], share["first_expert"],
            share["chips_sharing_a_layer"], share["vocab_size_published"],
            share["num_hidden_layers_published"]) == (128, 0, 16, 131072, 52)
    for key in ("no_rope", "grouped_gate_norm", "d_inner",
                "no_auxiliary_loss", "selection_bias", "initializer"):
        assert cfg["assumed"][key], key
    assert "16 chips" in cfg["deployment"] or "sixteen" in cfg["deployment"]
    assert (cell["chips"], cell["seq_len"], cell["traffic_name"]) == \
        (1, 8192, "steady")
    model = cells.load_module("models", "nemotron_h").build(cfg)
    c = model.config
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            c.mamba_heads, c.mamba_head_dim, c.n_groups, c.state_size,
            c.conv_kernel, c.chunk_size, c.expert_width, c.shared_width,
            c.vocab_size, c.pattern) == \
        (2688, 32, 2, 128, 64, 64, 8, 128, 4, 128, 1856, 3712, 16384,
         "MEMEM*EME")
    assert (c.num_experts, c.top_k, c.routed_scaling, c.experts_held,
            c.first_expert, c.norm_topk_prob) == (128, 6, 2.5, 8, 0, True)
    moe = c.moe_config()
    assert (moe.score_func, moe.selection_bias, moe.expert_act, moe.impl,
            moe.aux_loss) == ("sigmoid", True, "relu2", "grouped", "none")
    assert moe.bias_update_rate == \
        cell["config"]["train"]["selection_bias_update_rate"] == 0.05
    assert not c.attention_config().rope
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == 666_963_456


@pytest.mark.parametrize("key,value", [
    ("mlp_hidden_act", "silu"), ("attention_bias", True), ("n_group", 8),
    ("tie_word_embeddings", True), ("sliding_window", 4096),
    ("n_shared_experts", 2), ("num_hidden_layers", 10),
    ("use_conv_bias", False)])
def test_build_refuses_what_the_program_would_not_run_as_written(cell, key,
                                                                 value):
    cfg = dict(cell["config"], **{key: value})
    with pytest.raises(ValueError):
        cells.load_module("models", "nemotron_h").build(cfg)


def test_operation_counts_against_hand_arithmetic(cell):
    mod = cells.load_module("models", "nemotron_h")
    cfg = cell["config"]
    # per token, forward + backward, 6 FLOPs a matmul parameter passed
    mamba = 6 * (2688 * (4096 + 6144 + 64) + 4096 * 2688)    # 232.2 MFLOP
    scan = 3 * 6 * 64 * 128 * 64                             # 9.4
    attn = 6 * (2 * 2688 * 4096 + 2 * 2688 * 256)            # 140.4
    causal = 6 * 8192 * 4096                                 # 201.3
    expert = 6 * (2688 * 128 + 2 * 2688 * 3712
                  + 0.375 * 2 * 2688 * 1856)                 # 144.3
    head = 6 * 2688 * 16384                                  # 264.2
    assert mod.train_flops_per_token(cfg) == \
        4 * (mamba + scan) + attn + causal + 4 * expert + head
    att = mod.attention_cost_per_step(cfg, 2)
    kept = 8192 * 8193 // 2
    assert att["flops"] == 6 * 2 * 128 * kept * 2 * 32
    # q, o, dO, dq once a query head; k, v, dk, dv once a key/value head
    assert att["bytes"] == 6 * 2 * (32 + 2) * 8192 * 128 * 2
    moe = mod.moe_cost_per_step(cfg, 2)
    rows = 2 * 8192 * 6 * 8 // 128
    assert moe["flops"] == 4 * 6 * 2 * rows * 2688 * 1856
    assert moe["flops_fwd"] * 3 == moe["flops"]
    weights = 2 * 8 * 2688 * 1856 * 2
    assert moe["bytes"] == 4 * (5 * rows * 2688 * 2 + 3 * weights)
    ssd = mod.ssd_cost_per_step(cfg, 2)
    assert ssd["flops"] == 4 * 3 * 2 * 8192 * 6 * 64 * 128 * 64
    assert ssd["bytes"] == 4 * 2 * 2 * 8192 * (2 * 4096 + 2 * 1024 + 64) * 2
    assert ssd["flops_fwd"] * 3 == ssd["flops"]
    # the two bounds lie close: 3.1 ms of operations, 3.3 ms of bytes
    assert 0.8 < (ssd["flops"] / 197e12) / (ssd["bytes"] / 819e9) < 1.0


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        if m["name"] in LISTED:
            assert m["workloads"] == ["olmoe_1b_7b.steady", CELL]
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
    assert bench["workloads"][-1]["name"] == CELL
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | set(LISTED) <= names
    assert "step.collective_ms" not in names
    for m in cell["per_layer"]:  # every reader the cell asks for loads
        mod = cells.load_module("layer_metrics", m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
            (m["name"], m["unit"], m["layer"], m["moves"])


def test_new_readers_find_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """No trace, no events, a class without the parts: None, never an
    exception."""
    monkeypatch.setattr(program, "_table", {"fusion.1": "optimizer"})
    trace = {"devices": {"0": {"modules": [["jit_train_step(1)", 0, 10]],
                               "ops": [["fusion.1", 0, 10]]}}, "host": []}
    ledgers = {0: {"device": {"kind": "TPU v5 lite"}}}
    for cell_name in ("gpt2_124m.steady", "olmoe_1b_7b.steady", CELL):
        c = cells.load_cell(cell_name)
        for name in NEW:
            read = cells.load_module("layer_metrics", name).read
            assert read(trace, [], ledgers, c) is None, (cell_name, name)
            assert read(None, [], {}, c) is None, (cell_name, name)


def test_scan_readers_split_the_ssm_part_and_give_a_share_under_100(
        monkeypatch, cell):
    rules = program.part_rules("nemotron_h")
    assert rules["ssm"] == [["mamba"]] and rules["mlp"] == \
        program.part_rules("olmoe")["mlp"]
    top = "NemotronH/layers"
    table = {"fusion.1": f"fwd/{top}/mamba/in_proj",
             "fusion.2": f"fwd/{top}/mamba/conv",
             "fusion.3": f"bwd/{top}/mamba/ssd",
             "fusion.4": f"bwd/{top}/mamba/gate_norm",
             "fusion.5": f"fwd/{top}/feed_forward/moe/shared",
             "ragged-dot-none.1": "ragged_dot",
             "fusion.6": f"fwd/{top}/attention/q_proj",
             "fusion.7": f"fwd/{top}/norm", "fusion.8": "optimizer"}
    durs = {"fusion.1": 3e6, "fusion.2": 5e6, "fusion.3": 70e6,
            "fusion.4": 11e6, "fusion.5": 13e6, "ragged-dot-none.1": 17e6,
            "fusion.6": 19e6, "fusion.7": 23e6, "fusion.8": 29e6}
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
    assert read("step.moe_experts_ms") == 17.0
    assert read("step.moe_route_ms") == 13.0
    assert read("step.attn_dense_ms") == 19.0
    assert read("step.unscoped_ms") == 23.0
    # 3.30 ms of bytes at the published peak over 75 ms
    share = read("kernel.ssd_roofline")
    cost = cells.load_module("models", "nemotron_h").ssd_cost_per_step(
        cell["config"], cell["global_batch"])
    assert share == pytest.approx(100 * cost["bytes"] / 819e9 * 1e3 / 75.0)
    assert 4.0 < share < 5.0


def test_held_share_reader_takes_the_window_share_of_the_events(monkeypatch):
    read = cells.load_module("layer_metrics", "moe.held_rows_share").read
    events = [{"ev": "open", "t": 10.0, "t_sync": 10.0, "step": 10},
              {"ev": "close", "t": 20.0, "t_sync": 20.0, "step": 30}]
    spans = [{"name": "trainer:step_metrics", "t_mono": t, "dur_s": 0.0,
              "attrs": {"step": 0, "moe_dropped": 0.0,
                        "moe_load_max_over_mean": 2.0,
                        "moe_rows_held": held, "moe_rows_absent": absent}}
             for t, held, absent in ((5.0, 1.0, 1.0), (12.0, 6.0, 94.0),
                                     (18.0, 8.0, 92.0), (25.0, 1.0, 1.0))]
    spans.insert(2, {"name": "trainer:step_metrics", "t_mono": 15.0,
                     "dur_s": 0.0, "attrs": {"step": 0,
                                             "moe_load_max_over_mean": 1.0,
                                             "moe_dropped": 0.0}})
    monkeypatch.setattr(program, "setup_spans", lambda: spans)
    assert read(None, events, {}, cells.load_cell(CELL)) == \
        pytest.approx(7.0)
    assert read(None, events, {}, cells.load_cell("olmoe_1b_7b.steady")) \
        == pytest.approx(7.0)  # it reads counters, and names no model
    monkeypatch.setattr(program, "setup_spans", lambda: spans[2:3])
    assert read(None, events, {}, cells.load_cell(CELL)) is None


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, head_dim=32,
               num_attention_heads=4, num_key_value_heads=2,
               mamba_num_heads=8, mamba_head_dim=16, n_groups=2,
               ssm_state_size=16, chunk_size=16, intermediate_size=32,
               moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=48, n_routed_experts=2,
               num_experts_per_tok=2, hybrid_override_pattern="MEM*E",
               num_hidden_layers=5, max_position_embeddings=64)
    cfg["share"].update(n_routed_experts_published=8, first_expert=2)
    cfg["train"] = dict(cfg["train"], seq_len=64)
    cfg["program"] = dict(cfg["program"], dtype="float32",
                          use_flash_attention=False)
    return cfg


def test_reference_matches_program_at_nano_f32(cell):
    cfg = _nano(cell["config"])
    mod = cells.load_module("models", "nemotron_h")
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


def test_balanced_bias_evens_a_skewed_routers_load():
    """Scores with an offset an expert, as a fresh router on correlated
    tokens gives: under the solved bias every expert is among the k
    largest for T * k / E of the tokens, within a few of them."""
    mod = cells.load_module("models", "nemotron_h")
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    scores = jax.nn.sigmoid(jax.random.normal(keys[0], (4096, 32))
                            + 0.7 * jax.random.normal(keys[1], (32,)))

    def loads(bias):
        chosen = jax.lax.top_k(scores + bias, 4)[1]
        return jnp.bincount(chosen.reshape(-1), length=32)

    assert loads(0.0).max() > 3 * 512
    bias = mod.balanced_bias(scores, 4)
    assert abs(float(bias.mean())) < 1e-6
    assert int(jnp.abs(loads(bias) - 512).max()) <= 40


def test_the_seeded_state_starts_from_an_even_load(cell, tmp_path,
                                                   monkeypatch):
    """`seeded_state`: gpt's draw with the selection biases set on the
    seed's first batch so that the router's experts are chosen about
    equally often there; the same seed gives the same state, and every
    leaf but the biases is the plain draw's."""
    from dlrover_wuqiong_tpu.trainer.trainer import Trainer, TrainingArgs

    monkeypatch.setenv("DWT_JOB_NAME", f"bmseed{os.getpid()}")
    cfg = _nano(cell["config"])
    mod = cells.load_module("models", "nemotron_h")
    gpt = cells.load_module("models", "gpt")
    data = make_data(256, 8, 64, seed=11)
    args = dict(cell["traffic"]["training_args"], output_dir=str(tmp_path),
                global_batch_size=8, seq_len=64, strategy=[("fsdp", {})])
    tr = Trainer(mod.build(cfg), TrainingArgs(**args), data)
    try:
        plain = jax.tree.map(jnp.copy, gpt.seeded_state(tr, 11).params)
        first = jax.tree.map(jnp.copy, mod.seeded_state(tr, 11).params)
        again = mod.seeded_state(tr, 11).params
    finally:
        tr.ckpt.close()
    for (path, a), b, c in zip(
            jax.tree_util.tree_flatten_with_path(first)[0],
            jax.tree.leaves(again), jax.tree.leaves(plain)):
        assert (a == b).all(), path
        assert (path[-1].key == "selection_bias") != bool((a == c).all())
    _, found = tr.res.model.apply(
        {"params": first}, jnp.asarray(data(0)["input_ids"]),
        mutable=["intermediates"],
        capture_intermediates=lambda m, _: m.name == "router")
    for name in ("layers_1", "layers_4"):
        logits = found["intermediates"][name]["feed_forward"]["router"][
            "__call__"][0]
        bias = first[name]["feed_forward"]["selection_bias"]
        chosen = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, 2)[1]
        load = jnp.bincount(chosen.reshape(-1), length=8)
        assert int(jnp.abs(load - 128).max()) <= 16, (name, load)


def test_the_cells_control_flow_runs_on_the_cpu_at_nano_size(
        cell, monkeypatch, tmp_path):
    """`tests/rehearse.py`'s `NANO` is GPT-keyed, so the cell is rehearsed
    from here: the worker's own `train_process` — the seeded state, the
    check against the reference through the Trainer's compiled step, the
    window, the counters — on the CPU at a toy size.  Control flow only;
    no number of it means anything."""
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
    held = [s["attrs"] for s in program.setup_spans()
            if s["name"] == "trainer:step_metrics"]
    assert held and all(a["moe_dropped"] == 0.0 and a["moe_rows_held"] > 0
                        and a["moe_rows_absent"] > 0 for a in held)
    share = cells.load_module("layer_metrics", "moe.held_rows_share").read(
        None, run["events"], {}, cell)
    assert 5.0 < share < 60.0  # 2 of 8 experts: 25% under even routing
    assert cells.load_module("end_to_end", "tokens_per_s").read(
        None, run["events"], {0: rec}, cell) > 0
