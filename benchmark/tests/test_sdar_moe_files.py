"""The SDAR-30B-A3B-Chat configuration: published widths and the three
cuts, what `build` refuses, operation counts against hand arithmetic and
against a brute-force count of kept pairs and the program's leaf count,
the readers on its scopes file and on the program's counters (each None
on nothing), its plain reference against the program at a tiny size on
the CPU (both float32), and the cell's control flow rehearsed on the
CPU."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, program, worker
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

CELL = "sdar_30b_a3b.steady"
JOINED = ("step.moe_experts_ms", "step.moe_route_ms",
          "kernel.moe_gmm_roofline", "moe.load_max_over_mean",
          "moe.held_rows_share")
NEW = ("step.diffusion_noise_ms", "attn.bd_tiles_run_share",
       "attn.bd_kept_share", "diffusion.masked_share")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMS = 645_623_296


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "sdar_moe")


def _catalog_row() -> dict:
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "SDAR-30B" in line]
    return next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")


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
    assert (cfg["num_hidden_layers"], cfg["num_experts"]) == (6, 16)
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    share = cfg["share"]
    assert (share["num_experts_published"], share["first_expert"],
            share["ep"], share["vocabulary_slices"],
            share["num_hidden_layers_published"], share["pipeline_stages"],
            share["stage"], share["parameters"]) == \
        (128, 0, 8, 8, 48, 8, 1, PARAMS)
    taken = ("block_length", "noise", "prediction", "loss_mean",
             "mask_token", "positions", "mask", "qk_norm", "router",
             "auxiliary_loss")
    for key in taken + ("rope", "expert_form", "norms", "initializer",
                        "unused_keys"):
        assert cfg["assumed"][key], key
    for key in taken:
        assert "NOT TAKEN" in cfg["assumed"][key], key
    # what the row itself says it does not give is assumed, not guessed
    assert set(row["not_given"]) == {"block length", "noise schedule"}
    assert "first of eight" in cfg["deployment"]
    assert cfg["num_params"]["total"] == PARAMS
    assert cfg["num_params"]["uncut"] == 30_532_122_624
    assert (cfg["num_params"]["depth_5"], cfg["num_params"]["depth_4"]) \
        == (550_984_960, 456_346_624)
    assert "645,623,296" in cfg["num_params"]["sum"]
    rung = cfg["train"]["memory_rung"]
    assert rung["limit_GB"] == 14.4 and rung["taken"] == "a"
    # the issue's order: the first that passes is the one taken
    assert rung["live_GB"]["a: depth 6, 1 x 8192"] < 14.4
    assert len(rung["not_reached"]) == 2
    # 8,192 DATA tokens; 16,384 positions run, and the file says so
    assert (cell["chips"], cell["global_batch"], cell["seq_len"],
            cell["traffic_name"]) == (1, 1, 8192, "steady")
    assert "16,384 POSITIONS" in cfg["train"]["seq_len_note"]
    c = mod.build(cfg).config
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            c.num_experts, c.experts_held, c.first_expert, c.top_k,
            c.expert_width, c.vocab_size, c.num_layers, c.rope_theta,
            c.rms_eps, c.max_seq_len, c.block_length, c.noise_eps,
            c.noise_seed, c.mask_id) == \
        (2048, 32, 4, 128, 128, 16, 0, 8, 768, 18992, 6, 1e6, 1e-6, 32768,
         4, 1e-3, cfg["train"]["noise_seed"], 18991)
    llama = c.attention_config()
    assert llama.qk_head_norm and not llama.qk_norm
    assert llama.attn_block_diffusion == 4
    assert not c.moe_config().selection_bias \
        and not c.moe_config().shared_width
    assert c.router_aux_loss_weight == 0.01 \
        == cfg["train"]["router_aux_loss_coef"]
    assert c.moe_config().aux_loss == "topk"
    assert c.moe_config().aux_loss_weight == pytest.approx(0.01 / 6)
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == PARAMS


@pytest.mark.parametrize("key,value", [
    ("model_type", "qwen3_moe"), ("norm_topk_prob", False),
    ("attention_bias", True), ("use_sliding_window", True),
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
    ("num_key_value_heads", 5), ("max_position_embeddings", 4096)])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, key, value):
    with pytest.raises(ValueError):
        mod.build(dict(cell["config"], **{key: value}))


@pytest.mark.parametrize("seq,length", [(4, 4), (8, 4), (48, 2), (64, 16),
                                        (96, 8), (12, 1)])
def test_kept_pairs_is_a_brute_force_count(mod, seq, length):
    from benchmark import reference_sdar_moe

    mask = reference_sdar_moe.kept(jnp.arange(2 * seq), seq, length)
    assert mod.kept_pairs(seq, length) == int(mask.sum())


def test_operation_counts_against_hand_arithmetic_and_the_leaf_count(
        cell, mod):
    cfg, seq = cell["config"], cell["seq_len"]
    kept = seq * seq + seq * 4
    assert mod.kept_pairs(seq, 4) == kept == 67_141_632
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    router, routed, head = 2048 * 128, 1.0 * 3 * 2048 * 768, 2048 * 18992
    parts = mod.dense_params_per_token(cfg)
    # both copies through every product of every block, the head once
    assert parts["attention"] == 2 * 6 * attn
    assert parts["router"] == 2 * 6 * router
    assert parts["routed"] == 2 * 6 * routed == 2 * 6 * 4_718_592
    assert parts["head"] == head
    pairs = 6 * 2 * (128 + 128) * 32 * kept / seq
    assert mod.train_flops_per_token(cfg) == pytest.approx(
        6 * (2 * 6 * (attn + router + routed) + head) + 3 * pairs,
        rel=1e-12)
    # against the program's own leaves: attention, router and the 16
    # held experts are the tree's matmul leaves of a block (the norms'
    # 4,352 a block are no matmul), table and head half each of the rest
    leaves = mod.build(cfg).config.num_params()
    block = attn + 256 + 4096 + router + 16 * 4_718_592
    assert leaves == 6 * block + 2 * head + 2048 == 645_623_296
    att = mod.attention_cost_per_step(cfg, 1)
    assert att["flops"] == 6 * 6 * 2 * 128 * kept * 32
    # masked-away pairs are nobody's work: half of a causal call's over
    # the 16,384 positions, a quarter of the square's
    causal = 16384 * 16385 // 2
    assert att["flops"] * 2 == pytest.approx(
        6 * 6 * 2 * 128 * causal * 32, rel=1e-3)
    # q, o, dq, do once a query head; k, v and theirs once a kv head;
    # both copies' positions
    assert att["bytes"] == 6 * (6 * 32 + 6 * 4) * 2 * seq * 128 * 2
    assert att["flops"] / 197e12 > att["bytes"] / 819e9  # compute bound
    assert mod.attention_cost_per_step(cfg, 2)["flops"] == 2 * att["flops"]
    moe = mod.moe_cost_per_step(cfg, 1)
    assert moe["flops"] == 6 * 9 * 2 * (2 * seq) * 2048 * 768


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    assert bench["workloads"][-1]["name"] == CELL  # appended, at the end
    assert bench["configs"][-1]["name"] == cell["config_name"]
    assert len(bench["workloads"]) == 15
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(JOINED + NEW)
    for m in bench["per_layer"]:
        if m["name"] in JOINED:
            assert m["workloads"][-1] == CELL
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == list(NEW)
    assert bench["per_layer"][-4:] == new
    assert {m["layer"] for m in new} == {"block-diffusion layer", "kernels"}
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
    rules = program.part_rules("sdar_moe")
    assert list(rules) == ["optimizer", "head_loss", "diffusion", "mlp",
                           "attn_dense"]
    top = "SDAR/layers"
    at = f"{top}/attention"
    scopes = {"fwd/SDAR/diffusion/noise/threefry2x32": 5,
              "fwd/SDAR/diffusion/noise/concatenate": 2,
              f"fwd/{at}/q_proj": 13, f"fwd/{at}/o_proj": 17,
              f"fwd/{at}/qk_norm/q_norm": 8, f"fwd/{at}/rope": 4,
              f"fwd/{top}/feed_forward/moe/experts": 19,
              f"fwd/{top}/feed_forward/moe/dispatch": 23,
              f"fwd/{top}/input_norm": 3, "fwd/SDAR/head": 7,
              "bwd/loss": 37, "optimizer": 43}
    table = {f"fusion.{i}": s for i, s in enumerate(scopes)}
    ops, t = [], 0
    for i, ms in enumerate(scopes.values()):
        ops.append([f"fusion.{i}", t, ms * 1e6])
        t += ms * 1e6
    # the attention's own kernels: kernel.attn_ms's, in no part
    for name, ms in (("dwt_fa_bd_fwd.1", 60), ("dwt_fa_bd_bwd_dq.1", 50),
                     ("dwt_fa_bd_bwd_dkv.1", 90)):
        ops.append([name, t, ms * 1e6])
        t += ms * 1e6
    trace = {"devices": {"0": {"modules": [["jit_train_step(1)", 0, t]],
                               "ops": ops}}, "host": []}
    monkeypatch.setattr(program, "_table", table)
    ledgers = {0: {"device": {"kind": "TPU v5 lite"}}}

    def read(name, of=cell):
        return cells.load_module("layer_metrics", name).read(
            trace, [], ledgers, of)

    assert read("step.diffusion_noise_ms") == 5 + 2
    assert read("kernel.attn_ms") == 60 + 50 + 90
    assert read("step.attn_dense_ms") == 13 + 17
    assert read("step.mlp_ms") == 19 + 23
    assert read("step.moe_experts_ms") == 19
    assert read("step.moe_route_ms") == 23
    assert read("step.head_loss_ms") == 7 + 37
    assert read("step.unscoped_ms") == 8 + 4 + 3
    share = read("kernel.attn_roofline")
    cost = mod.attention_cost_per_step(cell["config"], cell["global_batch"])
    assert share == pytest.approx(100 * max(
        cost["flops"] / 197e12, cost["bytes"] / 819e9) * 1e3 / 200)
    assert 0 < share < 100
    # a step without the scope (the parent's program): nothing, no raise
    monkeypatch.setattr(program, "_table", {
        name: s.replace("diffusion/noise", "embed")
        for name, s in table.items()})
    assert read("step.diffusion_noise_ms") is None
    # a class without `diffusion_parts` (every other cell's): nothing
    monkeypatch.setattr(program, "_table", table)
    other = dict(cell, config=dict(cell["config"], model_class="keye_vl2"))
    assert read("step.diffusion_noise_ms", other) is None
    # no trace, no executable kept: nothing
    assert cells.load_module("layer_metrics", "step.diffusion_noise_ms") \
        .read(None, [], ledgers, cell) is None
    monkeypatch.setattr(program, "_table", None)
    assert read("step.diffusion_noise_ms") is None


def test_the_counter_readers_read_the_programs_counters(monkeypatch, cell):
    events = [{"ev": "open", "t": 1.0, "t_sync": 1.0, "gen": 0, "step": 10},
              {"ev": "close", "t": 9.0, "t_sync": 9.0, "gen": 0, "step": 20}]
    spans = [{"name": "trainer:step_metrics", "t_mono": t, "dur_s": 0.0,
              "attrs": {"step": 10, "attn_bd_tiles_run": run,
                        "attn_bd_tiles_live": 288.0,
                        "attn_bd_pairs_kept": 67141632.0,
                        "attn_bd_pairs_computed": run * 512 * 512,
                        "diffusion_masked_share": masked}}
             for t, run, masked in ((0.5, 1024.0, 0.0), (2.0, 288.0, 0.48),
                                    (5.0, 288.0, 0.52))]
    monkeypatch.setattr(program, "setup_spans", lambda: spans)

    def read(name):
        return cells.load_module("layer_metrics", name).read(
            None, events, {}, cell)

    assert read("attn.bd_tiles_run_share") == pytest.approx(100.0)
    assert read("attn.bd_kept_share") == pytest.approx(
        100 * 67141632 / (288 * 512 * 512))
    assert read("attn.bd_kept_share") == pytest.approx(88.9, abs=0.05)
    assert read("diffusion.masked_share") == pytest.approx(50.0)
    # a program without the counters (the parent commit): nothing, no raise
    for s in spans:
        for key in [k for k in s["attrs"]
                    if k.startswith(("attn_bd", "diffusion"))]:
            del s["attrs"][key]
    for name in NEW[1:]:
        assert read(name) is None, name


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               moe_intermediate_size=32, num_experts=4,
               num_experts_per_tok=3, max_position_embeddings=64)
    cfg["share"] = dict(cfg["share"], num_experts_published=16,
                        first_expert=4)
    cfg["train"] = dict(cfg["train"], seq_len=64)
    cfg["program"] = dict(cfg["program"], dtype="float32")
    return cfg


def test_reference_matches_program_at_nano_f32(cell, mod):
    cfg = _nano(cell["config"])
    model = mod.build(cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape),
        mod.seeded_leaves(model.init_params(jax.random.PRNGKey(3))))
    # the traffic's alphabet lies below MASK, the slice's last row
    batch = {k: jnp.asarray(v) for k, v in
             make_data(256, 4, 64, seed=3, alphabet=200)(0).items()}
    from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

    sys_loss, sys_norm = loss_and_grad_norm(make_lm_loss(model.apply),
                                            params, batch)
    ref_loss, ref_norm = loss_and_grad_norm(mod.reference_loss(cfg),
                                            params, batch,
                                            precision="highest")
    assert abs(sys_loss - ref_loss) / ref_loss < 1e-5
    assert abs(sys_norm - ref_norm) / ref_norm < 1e-4
    from benchmark import reference_sdar_moe

    for wrong in reference_sdar_moe.WRONG:
        off, off_norm = loss_and_grad_norm(
            mod.reference_loss(cfg, wrong=wrong), params, batch,
            precision="highest")
        assert abs(off - ref_loss) / ref_loss > 3e-6 \
            or abs(off_norm - ref_norm) / ref_norm > 1e-4, wrong


def test_the_cells_control_flow_runs_on_the_cpu_at_nano_size(
        cell, monkeypatch, tmp_path):
    """The worker's own `train_process` — the seeded state, the check
    against the reference through the Trainer's compiled step (a few
    sequences tiled over the batch: the repeats draw alike), the window —
    on the CPU at a toy size.  Control flow only; no number of it means
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

    def read(kind, name):
        return cells.load_module(kind, name).read(
            None, run["events"], {0: rec}, cell)

    assert read("end_to_end", "tokens_per_s") > 0
    from dlrover_wuqiong_tpu.ops.block_attention import bd_tile_count

    run_, live, kept, computed = bd_tile_count(64, 4, "plain")
    assert read("layer_metrics", "attn.bd_tiles_run_share") == \
        pytest.approx(100.0 * run_ / live)
    assert read("layer_metrics", "attn.bd_kept_share") == pytest.approx(
        100.0 * kept / computed)
    assert 20.0 < read("layer_metrics", "diffusion.masked_share") < 80.0
    assert 0.0 < read("layer_metrics", "moe.held_rows_share") < 100.0
    assert read("layer_metrics", "moe.load_max_over_mean") >= 1.0
