"""OLMoE through `models/llama.py` + `models/moe.py`: the dropless top-k
expert layer with un-normalised gates, QK-norm, the top-k load-balancing
loss and the router z-loss — against the plain reference
(`benchmark/reference_olmoe.py`) at a nano size on the CPU, float32 on
both sides, and the counters the step hands out.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_olmoe
from benchmark.reference import loss_and_grad_norm
from dlrover_wuqiong_tpu.models.llama import Llama, LlamaConfig
from dlrover_wuqiong_tpu.models.moe import (
    MoEConfig,
    MoEMLP,
    collect_moe_stats,
)
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

AUX_W, Z_W = 0.01, 0.001
VOCAB, SEQ, LAYERS, HEADS, EXPERTS, TOP_K = 256, 32, 2, 4, 8, 2


def nano(norm_topk_prob=False, **over):
    moe = MoEConfig(num_experts=EXPERTS, top_k=TOP_K, impl="grouped",
                    aux_loss_weight=AUX_W, z_loss_weight=Z_W,
                    aux_loss="topk", norm_topk_prob=norm_topk_prob,
                    dtype=jnp.float32)
    return LlamaConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=32,
        num_layers=LAYERS, num_heads=HEADS, num_kv_heads=HEADS,
        max_seq_len=SEQ, rope_theta=10000.0, dtype=jnp.float32,
        remat=False, use_flash_attention=False, moe=moe, qk_norm=True),
        **over})


def reference_loss(norm_topk_prob=False, aux_weight=AUX_W, z_weight=Z_W):
    return functools.partial(
        reference_olmoe.loss, n_layer=LAYERS, n_head=HEADS, top_k=TOP_K,
        norm_topk_prob=norm_topk_prob, eps=1e-5, theta=10000.0,
        aux_weight=aux_weight, z_weight=z_weight)


def seeded(cfg, seed=3):
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    # scales off 1 and a router with opinions, so that a missing norm or
    # a wrong gate shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * (1 + 0.3 * jax.random.normal(next(keys),
                                                         a.shape))
        if path[-1].key == "scale" else a, params)
    for i in range(cfg.num_layers):
        ff = params[f"layers_{i}"]["feed_forward"]
        ff["router"]["kernel"] = ff["router"]["kernel"] * 6.0
        for name in ("experts_w_gate", "experts_w_in", "experts_w_down"):
            ff[name] = ff[name] * 8.0  # normal(0.02) leaves the layer mute
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (4, SEQ + 1), 0,
                             VOCAB)
    return model, params, {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


# ------------------------------------------------- program vs reference

@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_total_loss_and_gradient_norm_match_the_reference(norm_topk_prob,
                                                          remat):
    model, params, batch = seeded(nano(norm_topk_prob, remat=remat))
    sys_loss, sys_norm = loss_and_grad_norm(make_lm_loss(model.apply),
                                            params, batch)
    ref_loss, ref_norm = loss_and_grad_norm(reference_loss(norm_topk_prob),
                                            params, batch,
                                            precision="highest")
    assert abs(sys_loss - ref_loss) / ref_loss < 1e-5
    assert abs(sys_norm - ref_norm) / ref_norm < 1e-4


LEAVES = [("layers_0", "feed_forward", "router", "kernel"),
          ("layers_1", "feed_forward", "router", "kernel"),
          ("layers_0", "attention", "q_norm", "scale"),
          ("layers_0", "attention", "k_norm", "scale"),
          ("layers_1", "feed_forward", "experts_w_gate"),
          ("layers_0", "feed_forward", "experts_w_in"),
          ("layers_0", "feed_forward", "experts_w_down"),
          ("lm_head", "kernel")]


@pytest.fixture(scope="module")
def both_gradients():
    model, params, batch = seeded(nano())
    got = jax.jit(jax.grad(make_lm_loss(model.apply)))(params, batch)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(reference_loss()))(params, batch)
    return got, want


@pytest.mark.parametrize("path", LEAVES, ids="/".join)
def test_per_leaf_gradient_matches_the_reference(both_gradients, path):
    got, want = both_gradients
    for key in path:
        got, want = got[key], want[key]
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 * scale, rtol=1e-4)


@pytest.mark.parametrize("wrong", ["renormalised", "no_z_loss",
                                   "no_qk_norm", "switch_aux"])
def test_a_wrong_term_is_outside_the_tolerance(wrong):
    """The check is tight: each variant a careless port would make moves
    the total loss or the gradient norm by more than the tolerances
    above."""
    cfg = nano()
    if wrong == "renormalised":
        cfg = nano(norm_topk_prob=True)
    elif wrong == "no_z_loss":
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, z_loss_weight=0.0))
    elif wrong == "switch_aux":
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, aux_loss="switch"))
    model, params, batch = seeded(cfg)
    if wrong == "no_qk_norm":
        model = Llama(dataclasses.replace(cfg, qk_norm=False))
    sys_loss, sys_norm = loss_and_grad_norm(make_lm_loss(model.apply),
                                            params, batch)
    ref_loss, ref_norm = loss_and_grad_norm(reference_loss(), params, batch,
                                            precision="highest")
    assert abs(sys_loss - ref_loss) / ref_loss > 1e-5 or \
        abs(sys_norm - ref_norm) / ref_norm > 1e-4


# ------------------------------------------------- the auxiliary terms

def _sown_aux(moe, logits_of_token):
    """The layer's sown auxiliary loss for hand-made router logits: the
    tokens are one-hot rows and the router kernel holds the logits."""
    n, e = logits_of_token.shape
    layer = MoEMLP(hidden=n, ffn=4, moe=moe)
    x = jnp.eye(n)[None]  # (1, n tokens, n features)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params["router"]["kernel"] = jnp.asarray(logits_of_token, jnp.float32)
    _, upd = layer.apply({"params": params}, x, mutable=["intermediates"])
    return upd["intermediates"]


@pytest.mark.parametrize("impl", ["grouped", "capacity"])
def test_load_balancing_term_is_the_formula_on_a_hand_made_routing(impl):
    # 4 tokens, 4 experts, top-2: tokens 0-2 choose {0, 1}, token 3 {2, 3}
    logits = np.log(np.array([[.4, .3, .2, .1], [.5, .3, .1, .1],
                              [.3, .4, .2, .1], [.1, .1, .4, .4]]))
    moe = MoEConfig(num_experts=4, top_k=2, impl=impl, aux_loss="topk",
                    aux_loss_weight=1.0, dtype=jnp.float32,
                    capacity_factor=4.0)
    inter = _sown_aux(moe, logits)
    f = np.array([3, 3, 1, 1]) / 4.0          # sums to top_k
    p = np.exp(logits).mean(0)
    want = 4 * float((f * p).sum())
    np.testing.assert_allclose(float(inter["moe_aux_loss"][0]), want,
                               rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(inter["moe_tokens_per_expert"][0]), [3, 3, 1, 1])


def test_z_loss_is_the_mean_squared_logsumexp():
    logits = np.array([[2.0, 0.0, 0.0, -1.0], [0.5, 0.5, 0.5, 0.5]])
    moe = MoEConfig(num_experts=4, top_k=1, impl="grouped", aux_loss="topk",
                    aux_loss_weight=0.0, z_loss_weight=1.0,
                    dtype=jnp.float32)
    inter = _sown_aux(moe, logits)
    lse = np.log(np.exp(logits).sum(-1))
    np.testing.assert_allclose(float(inter["moe_aux_loss"][0]),
                               float((lse ** 2).mean()), rtol=1e-6)


@pytest.mark.parametrize("term,weights", [("z_loss", (0.0, 0.5)),
                                          ("load_balancing", (0.5, 0.0))])
def test_each_auxiliary_term_reaches_the_steps_loss(term, weights):
    """`make_lm_loss` adds what is sown: the step's `loss` moves by the
    term's weight times the reference's own value of the term."""
    aux_w, z_w = weights
    base = nano()
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, aux_loss_weight=aux_w, z_loss_weight=z_w))
    off = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, aux_loss_weight=0.0, z_loss_weight=0.0))
    model, params, batch = seeded(cfg)
    with_term = float(jax.jit(make_lm_loss(model.apply))(params, batch))
    without = float(jax.jit(make_lm_loss(Llama(off).apply))(params, batch))
    want = float(jax.jit(reference_loss(aux_weight=aux_w, z_weight=z_w))(
        params, batch)) - float(jax.jit(reference_loss(
            aux_weight=0.0, z_weight=0.0))(params, batch))
    assert want > 1e-3
    np.testing.assert_allclose(with_term - without, want, rtol=1e-3)


# ------------------------------------------------- dropless, and counted

@pytest.mark.parametrize("impl", ["grouped", "capacity"])
def test_every_token_prefers_one_expert(impl):
    """All 16 tokens choose experts {0, 1}: the grouped path runs 16 rows
    through each and drops nothing; the capacity path (C = 1.25 * 16 * 2
    / 8 = 5 slots an expert) cannot keep 32 assignments in 10 slots, and
    says so in the same counter."""
    n, e = 16, 8
    logits = np.tile(np.array([5.0, 4.0] + [0.0] * (e - 2)), (n, 1))
    moe = MoEConfig(num_experts=e, top_k=2, impl=impl, dtype=jnp.float32)
    layer = MoEMLP(hidden=n, ffn=8, moe=moe)
    x = jnp.eye(n)[None]
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params["router"]["kernel"] = jnp.asarray(logits, jnp.float32)
    y, upd = layer.apply({"params": params}, x, mutable=["intermediates"])
    inter = upd["intermediates"]
    counts = np.asarray(inter["moe_tokens_per_expert"][0])
    assert int(inter["moe_dropped"][0]) == n * 2 - counts.sum()
    if impl == "grouped":
        np.testing.assert_array_equal(counts, [n, n] + [0] * (e - 2))
        assert int(inter["moe_dropped"][0]) == 0
        # every token's output is its two experts' weighted sum: none is 0
        assert float(jnp.abs(y[0]).sum(-1).min()) > 0
    else:
        assert int(inter["moe_dropped"][0]) > 0
    stats = collect_moe_stats(inter)
    np.testing.assert_allclose(float(stats["moe_load_max_over_mean"]),
                               counts.max() / counts.mean())


def test_the_step_hands_the_counters_out_beside_the_loss():
    import optax

    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate

    cfg = nano()
    res = auto_accelerate(Llama(cfg), optimizer=optax.adamw(1e-3),
                          strategy=[("fsdp", {})],
                          devices=jax.devices()[:1], seq_len=SEQ)
    _, _, batch = seeded(cfg)
    state, metrics = res.train_step(res.state, res.place_batch(dict(batch)))
    assert set(metrics) == {"loss", "grad_norm", "moe_load_max_over_mean",
                            "moe_dropped"}
    assert int(metrics["moe_dropped"]) == 0
    assert float(metrics["moe_load_max_over_mean"]) >= 1.0
    state, fused = res.fused_train_step(2)(
        state, jax.tree.map(lambda a: jnp.stack([a, a]),
                            res.place_batch(dict(batch))))
    assert int(fused["moe_dropped"]) == 0 and fused["losses"].shape == (2,)


def test_the_metrics_pump_passes_on_what_the_step_counted(tmp_path):
    """Whatever a step returns beside loss and grad_norm reaches the
    callbacks' dict and one `trainer:step_metrics` span event per logging
    boundary, read where the loss is; the Trainer names no model."""
    from dlrover_wuqiong_tpu.telemetry import spans as tspans
    from dlrover_wuqiong_tpu.trainer.trainer import Trainer, TrainingArgs

    cfg = nano()
    _, _, batch = seeded(cfg)
    # the test mesh has 8 devices: a batch they divide
    batch = {k: np.concatenate([v, v]) for k, v in batch.items()}
    got = []
    args = TrainingArgs(
        output_dir=str(tmp_path), max_steps=4, global_batch_size=8,
        seq_len=SEQ, warmup_steps=1, logging_steps=2, save_steps=0,
        fused_steps=1, perf_window_every=0, save_on_exit=False,
        resume=False, strategy=[("fsdp", {})])
    tr = Trainer(Llama(cfg), args, lambda step: dict(batch),
                 callbacks=[lambda step, m: got.append((step, m))])
    tspans.clear_spans()
    try:
        tr.train()
    finally:
        tr.ckpt.close()
    assert [step for step, _ in got] == [2, 4]
    for _, m in got:
        assert set(m) == {"loss", "tokens_per_sec",
                          "moe_load_max_over_mean", "moe_dropped"}
        assert m["moe_dropped"] == 0.0 and m["moe_load_max_over_mean"] >= 1
    events = [s for s in tspans.spans_snapshot()
              if s["name"] == "trainer:step_metrics"]
    assert [e["attrs"] for e in events] == [
        {"step": step, "moe_load_max_over_mean": m["moe_load_max_over_mean"],
         "moe_dropped": 0.0} for step, m in got]
    assert all("t_mono" in e for e in events)


def test_capacity_dispatch_refuses_unnormalised_gates():
    """`top_k_gating` always renormalises: the field cannot be honoured
    there, so the layer says so instead of computing something else."""
    moe = MoEConfig(num_experts=4, top_k=2, impl="capacity",
                    norm_topk_prob=False, dtype=jnp.float32)
    layer = MoEMLP(hidden=8, ffn=4, moe=moe)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 8)))


def test_a_dense_model_hands_out_no_counter():
    model = Llama(dataclasses.replace(LlamaConfig.nano(),
                                      dtype=jnp.float32,
                                      use_flash_attention=False))
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 9), jnp.int32)
    loss, stats = make_lm_loss(model.apply).with_stats(
        params, {"input_ids": ids[:, :-1], "labels": ids[:, 1:]})
    assert stats == {} and np.isfinite(float(loss))


# ------------------------------------------------- defaults change nothing

def test_new_llama_fields_default_to_the_dense_block():
    cfg = LlamaConfig.nano()
    assert cfg.moe is None and cfg.qk_norm is False
    model = Llama(dataclasses.replace(cfg, use_flash_attention=False))
    params = model.init_params(jax.random.PRNGKey(0))
    block = params["layers_0"]
    assert set(block["attention"]) == {"q_proj", "k_proj", "v_proj",
                                       "o_proj"}
    assert set(block["feed_forward"]) == {"gate_proj", "up_proj",
                                          "down_proj"}
    n = sum(a.size for a in jax.tree.leaves(params))
    assert n == cfg.num_params()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 512)
    spelled = Llama(dataclasses.replace(cfg, use_flash_attention=False,
                                        moe=None, qk_norm=False))
    np.testing.assert_array_equal(
        np.asarray(model.apply({"params": params}, ids)),
        np.asarray(spelled.apply({"params": params}, ids)))


def test_new_moe_fields_default_to_renormalised_switch_gating():
    """`MoEMLP` at the defaults is the layer `models/gpt.py` has always
    used: renormalised gates, Switch's top-1 loss, no z-loss — output and
    sown loss bit-identical to the fields spelled out, and equal to the
    formulas written out here."""
    cfg = MoEConfig(num_experts=4, top_k=2, dtype=jnp.float32)
    assert (cfg.norm_topk_prob, cfg.aux_loss, cfg.z_loss_weight,
            cfg.impl) == (True, "switch", 0.0, "capacity")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    layer = MoEMLP(hidden=16, ffn=32, moe=cfg)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert set(params) == {"router", "experts_w_in", "experts_w_gate",
                           "experts_w_down"}
    y, upd = layer.apply({"params": params}, x, mutable=["intermediates"])
    spelled = MoEMLP(hidden=16, ffn=32, moe=dataclasses.replace(
        cfg, norm_topk_prob=True, aux_loss="switch", z_loss_weight=0.0))
    y2, upd2 = spelled.apply({"params": params}, x,
                             mutable=["intermediates"])
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    aux = upd["intermediates"]["moe_aux_loss"][0]
    np.testing.assert_array_equal(
        np.asarray(aux), np.asarray(upd2["intermediates"]["moe_aux_loss"][0]))
    probs = jax.nn.softmax(x.reshape(16, 16) @ params["router"]["kernel"])
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), 4)
    np.testing.assert_allclose(
        float(aux), float((top1.mean(0) * probs.mean(0)).sum() * 16 * 0.01),
        rtol=1e-6)
    # the dense-dispatch output with renormalised gates, written out
    gates, experts = jax.lax.top_k(probs, 2)
    gates = gates / gates.sum(-1, keepdims=True)
    tok = x.reshape(16, 16)
    want = jnp.zeros_like(tok)
    for j in range(2):
        for e in range(4):
            h = jax.nn.silu(tok @ params["experts_w_gate"][e]) * \
                (tok @ params["experts_w_in"][e])
            want = want + jnp.where(
                (experts[:, j] == e)[:, None],
                gates[:, j, None] * (h @ params["experts_w_down"][e]), 0.0)
    np.testing.assert_allclose(np.asarray(y.reshape(16, 16)),
                               np.asarray(want), atol=1e-5)


# ------------------------------------------------- the parameter count

@pytest.mark.parametrize("depth,count", [(1, 625_616_896),
                                         (16, 6_919_161_856)])
def test_num_params_counts_experts_router_and_qk_norm(depth, count):
    cfg = LlamaConfig(
        vocab_size=50304, hidden_size=2048, intermediate_size=1024,
        num_layers=depth, num_heads=16, num_kv_heads=16, max_seq_len=4096,
        moe=MoEConfig(num_experts=64, top_k=8), qk_norm=True)
    assert cfg.num_params() == count  # the catalog's ~6.92B at depth 16


def test_num_params_is_the_tree_at_nano_size():
    cfg = nano()
    params = Llama(cfg).init_params(jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
