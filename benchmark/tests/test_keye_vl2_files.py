"""The Keye-VL-2.0-30B-A3B configuration: published widths and the four
cuts, what `build` refuses, operation counts against hand arithmetic and
against a brute-force count of kept pairs, the readers on its scopes
file and on the program's counters, its plain reference against the
program at a tiny size on the CPU (both float32), and the cell's control
flow rehearsed on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark import cells, program, worker
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm

CELL = "keye_vl2_30b_a3b.steady"
JOINED = ("step.moe_experts_ms", "step.moe_route_ms",
          "kernel.moe_gmm_roofline", "moe.load_max_over_mean",
          "moe.held_rows_share")
NEW = ("step.attn_index_ms", "step.attn_select_ms",
       "kernel.attn_index_roofline", "attn.sparse_kept_share",
       "attn.sparse_live_tiles_share", "attn.sparse_tiles_run_share")
REDUCED = ["num_hidden_layers", "num_experts", "num_local_experts",
           "vocab_size"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMS = 659_190_016


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def mod():
    return cells.load_module("models", "keye_vl2")


def _catalog_row() -> dict:
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "Keye-VL-2.0" in line]
    return next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")


def test_widths_are_the_catalog_rows_and_four_cuts_are_listed(cell, mod):
    cfg, row = cell["config"], _catalog_row()
    assert cfg["reduced"] == REDUCED == list(cfg["changed"])
    entry = next(c for c in cells.load_benchmark()["configs"]
                 if c["name"] == cell["config_name"])
    assert entry["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, published in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == published, key  # sa_config, rope_scaling whole
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["num_local_experts"]) == (6, 16, 16)
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    share = cfg["share"]
    assert (share["num_experts_published"], share["first_expert"],
            share["ep"], share["vocabulary_slices"],
            share["num_hidden_layers_published"], share["pipeline_stages"],
            share["stage"], share["parameters"]) == \
        (128, 0, 8, 8, 48, 8, 1, PARAMS)
    for key in ("qk_norm", "mrope", "indexer", "indexer_rope", "choice",
                "index_loss", "router", "expert_form", "norms",
                "auxiliary_loss", "initializer", "unused_keys"):
        assert cfg["assumed"][key], key
    for key in ("qk_norm", "mrope", "indexer", "indexer_rope", "choice",
                "index_loss", "router"):
        assert "NOT TAKEN" in cfg["assumed"][key], key
    assert "first of eight" in cfg["deployment"]
    assert cfg["num_params"]["total"] == PARAMS
    assert cfg["num_params"]["uncut"] == 30_640_656_384
    assert cfg["num_params"]["issue_depth_5"] == 562_290_560
    assert "659,190,016" in cfg["num_params"]["sum"]
    rung = cfg["train"]["memory_rung"]
    assert rung["limit_GB"] == 14.4 and len(rung["live_GB"]) == 4
    by_letter = {k[0]: v for k, v in rung["live_GB"].items()}
    # the issue's order: the first that passes is the one taken
    assert by_letter["a"] > 14.4 and by_letter["b"] > 14.4 \
        and by_letter["c"] < 14.4 and rung["taken"] == "c"
    assert (cell["chips"], cell["global_batch"], cell["seq_len"],
            cell["traffic_name"]) == (1, 1, 16384, "steady")
    c = mod.build(cfg).config
    assert (c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            c.index_topk, c.index_heads, c.index_dim, c.num_experts,
            c.experts_held, c.first_expert, c.top_k, c.expert_width,
            c.vocab_size, c.num_layers, c.rope_theta, c.rms_eps,
            c.max_seq_len, c.mrope_sections, c.index_loss_weight) == \
        (2048, 32, 4, 128, 2048, 16, 64, 128, 16, 0, 8, 768, 18992, 6, 1e7,
         1e-6, 262144, (16, 24, 24), 1.0)
    llama = c.attention_config()
    assert llama.qk_head_norm and not llama.qk_norm
    assert not c.moe_config().selection_bias \
        and not c.moe_config().shared_width
    # the balance term the file assumes (and says why), a layer's sixth
    assert c.router_aux_loss_weight == 0.01 \
        == cfg["train"]["router_aux_loss_coef"]
    assert c.moe_config().aux_loss == "topk"
    assert c.moe_config().aux_loss_weight == pytest.approx(0.01 / 6)
    assert "NOT TAKEN" in cfg["assumed"]["auxiliary_loss"]
    assert (c.remat, c.remat_policy) == (True, "full")
    assert c.num_params() == PARAMS


@pytest.mark.parametrize("key,value", [
    ("model_type", "qwen3_moe"), ("norm_topk_prob", False),
    ("attention_bias", True), ("use_sliding_window", True),
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("rope_scaling", {"mrope_section": [16, 24, 24], "rope_type": "yarn",
                      "type": "yarn"}),
    ("num_key_value_heads", 5), ("max_position_embeddings", 4096)])
def test_build_refuses_what_the_program_would_not_run_as_written(
        cell, mod, key, value):
    with pytest.raises(ValueError):
        mod.build(dict(cell["config"], **{key: value}))


def test_an_indexer_of_several_keys_is_refused(cell, mod):
    sa = dict(cell["config"]["sa_config"], indexer_num_kv_heads=2)
    with pytest.raises(ValueError, match="ONE shared key"):
        mod.build(dict(cell["config"], sa_config=sa))


@pytest.mark.parametrize("seq,topk", [(1, 4), (4, 4), (5, 4), (48, 16),
                                      (64, 64), (100, 7)])
def test_kept_pairs_is_a_brute_force_count(mod, seq, topk):
    assert mod.kept_pairs(seq, topk) == sum(
        min(topk, t + 1) for t in range(seq))
    assert mod.causal_pairs(seq) == sum(t + 1 for t in range(seq))


def test_operation_counts_against_hand_arithmetic(cell, mod):
    cfg, seq = cell["config"], cell["seq_len"]
    kept = 2048 * 2049 // 2 + (seq - 2048) * 2048
    causal = seq * (seq + 1) // 2
    assert mod.kept_pairs(seq, 2048) == kept
    assert round(100 * kept / causal, 1) == 23.4
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16
    router, routed, head = 2048 * 128, 1.0 * 3 * 2048 * 768, 2048 * 18992
    parts = mod.dense_params_per_token(cfg)
    assert parts["attention"] == 6 * attn
    assert parts["indexer"] == 6 * indexer
    assert parts["routed"] == 6 * routed == 6 * 4_718_592
    main_pairs = 6 * 2 * (128 + 128) * 32 * kept / seq
    index_pairs = 6 * 2 * 64 * 16 * causal / seq
    assert mod.train_flops_per_token(cfg) == pytest.approx(
        6 * (6 * (attn + router + routed) + head) + 4 * 6 * indexer
        + 3 * (main_pairs + index_pairs), rel=1e-12)
    att = mod.attention_cost_per_step(cfg, 1)
    assert att["flops"] == 6 * 6 * 2 * 128 * kept * 32
    # masked-away pairs are nobody's work: under a quarter of the causal
    assert att["flops"] * 4 < 6 * 6 * 2 * 128 * causal * 32 * 1.0001 \
        and att["flops"] * 5 > 6 * 6 * 2 * 128 * causal * 32
    # q, o, dq, do once a query head; k, v and theirs once a kv head
    assert att["bytes"] == 6 * (6 * 32 + 6 * 4) * seq * 128 * 2
    idx = mod.index_cost_per_step(cfg, 1)
    assert idx["flops_fwd"] == 6 * 2 * 64 * 16 * causal
    assert idx["flops"] == 3 * idx["flops_fwd"]
    assert idx["bytes_fwd"] == 6 * (seq * (1024 + 64) * 2 + seq * 16 * 4
                                    + 4 * causal)
    assert idx["flops"] / 197e12 > idx["bytes"] / 819e9  # compute bound
    assert mod.index_cost_per_step(cfg, 2)["flops"] == 2 * idx["flops"]
    moe = mod.moe_cost_per_step(cfg, 1)
    assert moe["flops"] == 6 * 9 * 2 * seq * 2048 * 768


def test_benchmark_json_lists_the_cell_where_it_reports(cell):
    bench = cells.load_benchmark()
    assert bench["workloads"][-1]["name"] == CELL  # appended, at the end
    assert bench["configs"][-1]["name"] == cell["config_name"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(JOINED + NEW)
    for m in bench["per_layer"]:
        if m["name"] in JOINED:
            assert m["workloads"][-1] == CELL
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == list(NEW)
    assert bench["per_layer"][-6:] == new
    assert {m["layer"] for m in new} == {"sparse-attention layer", "kernels"}
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
    rules = program.part_rules("keye_vl2")
    assert list(rules) == ["optimizer", "head_loss", "attn_sparse", "mlp",
                           "attn_dense"]
    top = "Keye/layers"
    at = f"{top}/attention"
    sp = f"{at}/sparse_attn"
    scopes = {f"fwd/{at}/indexer/sparse_attn/index/wq_idx": 11,
              f"fwd/{sp}/index/scores/dwt_idx_scores": 40,
              f"recompute/{sp}/index/scores/dwt_idx_scores": 41,
              f"bwd/{sp}/index_loss/scores/dwt_idx_bwd": 90,
              f"fwd/{sp}/index_loss/dwt_idx_kl": 70,
              f"fwd/{sp}/select/dwt_idx_select": 50,
              f"recompute/{sp}/select/reshape": 2,
              f"bwd/{sp}/attend/mul": 6, f"fwd/{sp}/counters/reduce": 1,
              f"fwd/{at}/q_proj": 13, f"fwd/{at}/o_proj": 17,
              f"fwd/{at}/qk_norm/q_norm": 8, f"fwd/{at}/rope": 4,
              f"fwd/{top}/feed_forward/moe/experts": 19,
              f"fwd/{top}/feed_forward/moe/dispatch": 23,
              f"fwd/{top}/input_norm": 3, "fwd/Keye/head": 7,
              "bwd/loss": 37, "optimizer": 43}
    table = {f"fusion.{i}": s for i, s in enumerate(scopes)}
    ops, t = [], 0
    for i, ms in enumerate(scopes.values()):
        ops.append([f"fusion.{i}", t, ms * 1e6])
        t += ms * 1e6
    # the main attention's own kernel: kernel.attn_ms's, in no part
    ops.append(["dwt_fa_sp_fwd.1", t, 100e6])
    trace = {"devices": {"0": {"modules": [["jit_train_step(1)", 0,
                                            t + 100e6]],
                               "ops": ops}}, "host": []}
    monkeypatch.setattr(program, "_table", table)
    ledgers = {0: {"device": {"kind": "TPU v5 lite"}}}

    def read(name):
        return cells.load_module("layer_metrics", name).read(
            trace, [], ledgers, cell)

    scores = 40 + 41 + 90
    assert read("step.attn_index_ms") == scores + 11 + 70
    assert read("step.attn_select_ms") == 50 + 2
    assert read("kernel.attn_ms") == 100
    assert read("step.attn_dense_ms") == 13 + 17
    assert read("step.mlp_ms") == 19 + 23
    assert read("step.moe_experts_ms") == 19
    assert read("step.moe_route_ms") == 23
    assert read("step.head_loss_ms") == 7 + 37
    assert read("step.unscoped_ms") == 8 + 4 + 3
    share = read("kernel.attn_index_roofline")
    cost = mod.index_cost_per_step(cell["config"], cell["global_batch"])
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert share == pytest.approx(100 * least * 1e3 / scores)
    assert 0 < share < 100
    main = read("kernel.attn_roofline")
    cost = mod.attention_cost_per_step(cell["config"], cell["global_batch"])
    assert main == pytest.approx(100 * max(
        cost["flops"] / 197e12, cost["bytes"] / 819e9) * 1e3 / 100)
    # a step without the scopes (the parent's program): nothing, no raise
    monkeypatch.setattr(program, "_table", {
        name: s.replace("sparse_attn", "attn") for name, s in table.items()})
    for name in ("step.attn_index_ms", "step.attn_select_ms",
                 "kernel.attn_index_roofline"):
        assert read(name) is None, name
    # a class without `sparse_parts` (every other cell's): nothing
    other = dict(cell, config=dict(cell["config"], model_class="lfm2_moe"))
    monkeypatch.setattr(program, "_table", table)
    for name in ("step.attn_index_ms", "step.attn_select_ms",
                 "kernel.attn_index_roofline"):
        assert cells.load_module("layer_metrics", name).read(
            trace, [], ledgers, other) is None, name


def test_the_counter_readers_read_the_programs_counters(monkeypatch, cell):
    events = [{"ev": "open", "t": 1.0, "t_sync": 1.0, "gen": 0, "step": 10},
              {"ev": "close", "t": 9.0, "t_sync": 9.0, "gen": 0, "step": 20}]
    spans = [{"name": "trainer:step_metrics", "t_mono": t, "dur_s": 0.0,
              "attrs": {"step": 10, "attn_sparse_kept": kept,
                        "attn_sparse_causal": 1000.0,
                        "attn_sparse_live_tiles": live,
                        "attn_sparse_tiles_causal": 40.0,
                        "attn_sparse_tiles_run": 40.0}}
             for t, kept, live in ((0.5, 0.0, 0.0), (2.0, 234.0, 40.0),
                                   (5.0, 234.0, 30.0))]
    monkeypatch.setattr(program, "setup_spans", lambda: spans)

    def read(name):
        return cells.load_module("layer_metrics", name).read(
            None, events, {}, cell)

    assert read("attn.sparse_kept_share") == pytest.approx(23.4)
    assert read("attn.sparse_live_tiles_share") == pytest.approx(87.5)
    assert read("attn.sparse_tiles_run_share") == pytest.approx(100.0)
    # a program without the counters (the parent commit): nothing, no raise
    for s in spans:
        for key in [k for k in s["attrs"] if k.startswith("attn_sparse")]:
            del s["attrs"][key]
    for name in NEW[3:]:
        assert read(name) is None, name


def _nano(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    cfg.update(vocab_size=256, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               moe_intermediate_size=32, num_experts=4, num_local_experts=4,
               num_experts_per_tok=3, max_position_embeddings=64)
    cfg["sa_config"] = dict(cfg["sa_config"], indexer_head_dim=8,
                            indexer_num_heads=2, topk=16)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], mrope_section=[2, 2, 4])
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
    from benchmark import reference_keye_vl2

    for wrong in reference_keye_vl2.WRONG:
        off, off_norm = loss_and_grad_norm(
            mod.reference_loss(cfg, wrong=wrong), params, batch,
            precision="highest")
        moved = abs(off - ref_loss) / ref_loss > 3e-6 \
            or abs(off_norm - ref_norm) / ref_norm > 1e-4
        # text cannot see the sections' order; every other wrong
        # equation is another number
        assert moved == (wrong != "mrope_sections"), wrong


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

    def read(kind, name):
        return cells.load_module(kind, name).read(
            None, run["events"], {0: rec}, cell)

    assert read("end_to_end", "tokens_per_s") > 0
    from benchmark.models.keye_vl2 import causal_pairs, kept_pairs  # noqa

    assert read("layer_metrics", "attn.sparse_kept_share") == pytest.approx(
        100.0 * kept_pairs(64, 16) / causal_pairs(64))
    assert read("layer_metrics", "attn.sparse_tiles_run_share") == 100.0
    assert read("layer_metrics", "attn.sparse_live_tiles_share") == 100.0
    assert 0.0 < read("layer_metrics", "moe.held_rows_share") < 100.0
    assert read("layer_metrics", "moe.load_max_over_mean") >= 1.0
