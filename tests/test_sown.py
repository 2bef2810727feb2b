"""What a model hands the step (PR 68): `models/sown.py` is the one place
the step asks, the file that sows a value says there what it is, and
`trainer/` and `parallel/` name no model's counter, term or module.

All on the CPU at nano sizes; the models of (B) are traced, never
compiled.
"""

import ast
import dataclasses
import pathlib
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_wuqiong_tpu.models import (
    attention,
    bailing_hybrid,
    gated_delta,
    gpt,
    granite_hybrid,
    hyper_connection,
    kda,
    keye,
    laguna,
    latent_moe,
    lfm2,
    llama,
    moe,
    nemotron_h,
    olmo_hybrid,
    phi4flash,
    qwen3_next,
    sdar,
    smallthinker,
)
from dlrover_wuqiong_tpu.models import sown as handed
from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

PACKAGE = pathlib.Path(handed.__file__).parents[1]
F32 = np.float32


def bits(x):
    return np.asarray(x, F32).tobytes().hex()


def layers(*sown_by_layer):
    """An `intermediates` collection as flax hands it out: one dict of
    sown tuples a layer."""
    return {f"layers_{i}": {"mixer": {name: (jnp.asarray(value, F32),)
                                      for name, value in sown.items()}}
            for i, sown in enumerate(sown_by_layer)}


@pytest.fixture
def registry(monkeypatch):
    """The registrants of this process on tables of the test's own: what
    a test registers, or reorders, is gone with it."""
    for table in ("_COUNTERS", "_TERMS", "_STEPS", "_OBJECTIVES"):
        monkeypatch.setattr(handed, table, dict(getattr(handed, table)))
    return handed


# ------------------------- (A) every registrant is reached through collect

BATCH = {"labels": jnp.asarray([[3, 1, 4, 1, 5], [9, 2, 6, 5, 3]])}
CE = jnp.asarray(5.4321, F32)
MTP_LOGITS = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 16), F32)
REGISTRANTS = {
    "collect_moe_stats": (moe, "counters", layers(
        dict(moe_tokens_per_expert=[3, 1, 4, 0], moe_dropped=2.0,
             moe_rows_held=5.0, moe_rows_absent=3.0, moe_gmm_tiles=[3, 4]),
        dict(moe_tokens_per_expert=[2, 2, 2, 2], moe_dropped=0.0,
             moe_rows_held=8.0, moe_rows_absent=0.0, moe_gmm_tiles=[4, 4],
             moe_shared_gate_mean=0.25))),
    "collect_moe_aux_loss": (moe, "term", layers(
        dict(moe_aux_loss=0.0123), dict(moe_aux_loss=[0.5, 0.004]))),
    "collect_param_steps": (moe, "steps", layers(
        dict(moe_selection_bias_step=[0.05, -0.05, 0.0]),
        dict(moe_dropped=0.0),
        dict(moe_selection_bias_step=[-0.05, 0.0, 0.05]))),
    "collect_attention_stats": (attention, "counters", layers(
        dict(attn_tiles=[6, 10], attn_gate_mean=0.5, attn_gate_kernel=1.0),
        dict(attn_lanes=[512, 320], attn_gate_mean=0.25,
             attn_gate_kernel=0.0),
        dict(attn_sparse=[40, 90, 3, 4, 4], attn_index_kl=0.7))),
    "collect_attention_aux_loss": (attention, "term", layers(
        dict(attn_index_loss=0.3), dict(attn_index_loss=0.0625))),
    "collect_delta_stats": (gated_delta, "counters", layers(
        dict(delta_stats=[256, 128, 30.5, 17.25, 64]),
        dict(delta_stats=[256, 128, 60.0, 3.5, 64]))),
    "collect_kda_stats": (kda, "counters", layers(
        dict(kda_stats=[3, 128, 40.0, 64]),
        dict(kda_stats=[0, 128, 31.0, 64]))),
    "collect_residual_stats": (hyper_connection, "counters", layers(
        dict(hc_sinkhorn_err=[1e-6, 3e-5]), dict(hc_sinkhorn_err=[2e-5]))),
    "collect_mtp_loss": (latent_moe, "term", {
        "mtp_logits": (MTP_LOGITS,),
        "mtp_loss_weight": (jnp.asarray(0.3, F32),)}),
    "collect_shortconv_stats": (lfm2, "counters", layers(
        dict(shortconv_calls=[1, 1]), dict(shortconv_calls=[0, 1]))),
    "collect_diffusion_stats": (sdar, "counters", {
        "diffusion_noise": (jnp.asarray([0.5, 0.97], F32),)}),
    "collect_phi4flash_stats": (phi4flash, "counters", layers(
        dict(attn_diff_lambda=0.36), dict(gmu_gate_mean=0.21),
        dict(attn_diff_lambda=0.8))),
}


@pytest.mark.parametrize("name", sorted(REGISTRANTS))
def test_a_registrant_is_reached_through_collect(name):
    module, kind, inter = REGISTRANTS[name]
    fn = getattr(module, name)
    loss, stats = handed.collect(inter, BATCH, CE)
    if kind == "counters":
        direct = fn(inter)
        assert direct and loss is CE  # no term: nothing is added
        assert {k: bits(v) for k, v in stats.items()} \
            == {k: bits(v) for k, v in direct.items()}
    elif kind == "steps":
        direct = fn(inter)
        assert set(direct) == {"layers_0", "layers_2"}
        assert jax.tree.map(bits, stats) \
            == {"param_steps": jax.tree.map(bits, direct)}
    elif name == "collect_moe_aux_loss":  # a scalar: `moe_aux_term` adds it
        assert stats == {} and bits(loss) == bits(CE + fn(inter))
        assert float(loss) > float(CE)
    else:
        term, more = fn(inter, BATCH, CE)
        assert bits(loss) == bits(CE + term) and more
        assert {k: bits(v) for k, v in stats.items()} \
            == {k: bits(v) for k, v in more.items()}
    # and nothing of it without the leaves it reads
    assert handed.collect(layers(dict(other=1.0)), BATCH, CE)[1] == {}


def test_the_ten_are_all_that_is_registered():
    """Twelve since PR 72 (the differential attentions' and the memory
    units' counters), and ONE objective."""
    assert {key.rsplit(".", 1)[1] for table in (
        handed._COUNTERS, handed._TERMS, handed._STEPS) for key in table} \
        == (set(REGISTRANTS) - {"collect_moe_aux_loss"}) | {"moe_aux_term"}
    assert all(key.startswith("dlrover_wuqiong_tpu.models.")
               for table in (handed._COUNTERS, handed._TERMS, handed._STEPS,
                             handed._OBJECTIVES)
               for key in table)
    assert list(handed._OBJECTIVES) == [
        "dlrover_wuqiong_tpu.models.sdar.diffusion_objective"]


# ------------- (B) every model class of the cells: the keys the step gets

NANO = dict(dtype=jnp.float32, remat=False)
PLAIN = dict(use_flash_attention=False)
HELD = dict(experts_held=4, first_expert=4)
XING = dict(num_layers=2, residual_lanes=4, q_lora_rank=12, top_k=2,
            rope_scaling=llama.RopeScaling(
                factor=4.0, original_max_position_embeddings=16,
                beta_fast=4, beta_slow=1, mscale=1, mscale_all_dim=1),
            experts_held=4, first_expert=2, bias_update_rate=0.05, **NANO)
MODELS = {
    "gpt": lambda: gpt.GPT(dataclasses.replace(
        gpt.GPTConfig.nano(), dtype=jnp.float32, **PLAIN)),
    "llama_dense": lambda: llama.Llama(dataclasses.replace(
        llama.LlamaConfig.nano(), dtype=jnp.float32, **PLAIN)),
    "olmoe": lambda: llama.Llama(llama.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=2,
        num_heads=4, num_kv_heads=4, max_seq_len=64, qk_norm=True,
        moe=moe.MoEConfig(num_experts=8, top_k=2, impl="grouped",
                          aux_loss="topk", z_loss_weight=0.001,
                          norm_topk_prob=False, dtype=jnp.float32),
        **NANO, **PLAIN)),
    "nemotron_h": lambda: nemotron_h.NemotronH(
        nemotron_h.NemotronHConfig.nano(
            bias_update_rate=0.05, **NANO, **PLAIN)),
    "granite_hybrid": lambda: granite_hybrid.GraniteHybrid(
        granite_hybrid.GraniteHybridConfig.nano(**NANO, **PLAIN)),
    "smallthinker": lambda: smallthinker.SmallThinker(
        smallthinker.SmallThinkerConfig.nano(**NANO)),
    "latent_moe_kimi": lambda: latent_moe.LatentMoE(
        latent_moe.LatentMoEConfig.nano(bias_update_rate=0.05, **NANO)),
    "latent_moe_xing": lambda: latent_moe.LatentMoE(
        latent_moe.LatentMoEConfig.nano(**XING)),
    "latent_moe_xing_mtp": lambda: latent_moe.LatentMoE(
        latent_moe.LatentMoEConfig.nano(**XING, mtp_layers=1)),
    "olmo_hybrid": lambda: olmo_hybrid.OlmoHybrid(
        olmo_hybrid.OlmoHybridConfig.nano(**NANO, **PLAIN)),
    "laguna": lambda: laguna.Laguna(laguna.LagunaConfig.nano(**NANO)),
    "bailing_hybrid": lambda: bailing_hybrid.BailingHybrid(
        bailing_hybrid.BailingHybridConfig.nano(
            num_layers=3, bias_update_rate=0.05, **NANO, **PLAIN, **HELD)),
    "lfm2": lambda: lfm2.Lfm2(lfm2.Lfm2Config.nano(
        bias_update_rate=0.05, **NANO, **PLAIN, **HELD)),
    "keye": lambda: keye.Keye(keye.KeyeConfig.nano(**NANO, **HELD)),
    "qwen3_next": lambda: qwen3_next.Qwen3Next(
        qwen3_next.Qwen3NextConfig.nano(**NANO, **HELD)),
    "phi4flash": lambda: phi4flash.Phi4Flash(
        phi4flash.Phi4FlashConfig.nano(**NANO)),
}
# the parent's (18f8494) `with_stats` on these configurations, key by key
MOE = {"moe_dropped", "moe_load_max_over_mean"}
SHARE = MOE | {"moe_rows_held", "moe_rows_absent", "moe_gmm_tiles_share",
               "moe_map_tiles_share", "moe_gather_rows_share",
               "moe_combine_rows_share"}
LANES = {"attn_lanes_run", "attn_lanes_model"}
TILES = {"attn_tiles_window", "attn_tiles_causal"}
DELTA = {"delta_lanes_run", "delta_lanes_model", "delta_alpha_mean",
         "delta_beta_mean"}
STEPS = {"param_steps"}
KEYS = {
    "gpt": set(),
    "llama_dense": set(),
    "olmoe": MOE,
    "nemotron_h": MOE | STEPS,
    "granite_hybrid": set(),
    "smallthinker": MOE | TILES,
    "latent_moe_kimi": MOE | LANES | STEPS,
    "latent_moe_xing": SHARE | LANES | STEPS | {"resmix_sinkhorn_err"},
    "latent_moe_xing_mtp": SHARE | LANES | STEPS | {"resmix_sinkhorn_err",
                                                    "mtp_ce"},
    "olmo_hybrid": DELTA,
    "laguna": MOE | TILES | {"attn_pairs_kept", "attn_pairs_computed",
                             "attn_gate_mean", "attn_gate_kernel_share"},
    "bailing_hybrid": SHARE | LANES | DELTA | STEPS | {
        "attn_gate_mean", "kda_decay_floor_share", "kda_gate_mean",
        "moe_group_limit_binds"},
    "lfm2": SHARE | STEPS | {"shortconv_calls", "shortconv_plain_calls"},
    "keye": SHARE | {"ce", "index_kl", "attn_sparse_kept",
                     "attn_sparse_causal", "attn_sparse_live_tiles",
                     "attn_sparse_tiles_causal", "attn_sparse_tiles_run"},
    "qwen3_next": SHARE | LANES | DELTA | {
        "attn_gate_mean", "delta_qk_rows_run", "delta_qk_rows_model",
        "moe_shared_gate_mean"},
    "phi4flash": LANES | TILES | {"attn_diff_lambda_mean", "gmu_gate_mean"},
}


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_model_class_hands_the_step_the_recorded_keys(name):
    model = MODELS[name]()
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, 48), jnp.int32)
    loss, stats = jax.eval_shape(make_lm_loss(model.apply).with_stats,
                                 params, {"input_ids": ids, "labels": ids})
    assert set(stats) == KEYS[name]
    assert (loss.shape, loss.dtype) == ((), jnp.float32)
    assert all(v.shape == () for k, v in stats.items() if k != "param_steps")


# ---------------------------------- (C) the loss's order of summation

def test_the_terms_join_the_loss_in_one_order_whatever_was_imported_first(
        registry):
    """((ce + MoE aux) + the indexers' KL) + MTP, bit for bit: float32
    addition is not associative.  At 2 ** 24 a float32 steps by 2: a term
    of 1 is a tie and goes to the even neighbour, so with terms of 1, 1
    and 1.2 the declared order reads + 2 and the others below + 4."""
    ce = jnp.asarray(2.0 ** 24, F32)
    inter = {**layers(dict(moe_aux_loss=1.0), dict(attn_index_loss=1.0)),
             "mtp_logits": (MTP_LOGITS,),
             "mtp_loss_weight": (jnp.asarray(0.43, F32),)}
    aux = moe.collect_moe_aux_loss(inter)
    kl, _ = attention.collect_attention_aux_loss(inter, BATCH, ce)
    mtp, _ = latent_moe.collect_mtp_loss(inter, BATCH, ce)
    assert 1.1 < float(mtp) < 1.3
    want = ((ce + aux) + kl) + mtp
    assert float(want - ce) == 2.0
    for other in (((ce + aux) + mtp) + kl, ((ce + mtp) + kl) + aux,
                  ce + ((aux + kl) + mtp)):
        assert float(other - ce) == 4.0
    loss, stats = registry.collect(inter, BATCH, ce)
    assert bits(loss) == bits(want)
    assert set(stats) == {"ce", "mtp_ce"} and bits(stats["ce"]) == bits(ce)
    # the tables' own order is the order of import: turned round, and
    # with a term sown.py does not name, the declared three still lead
    late = registry.term(lambda inter, batch, ce: (jnp.asarray(1.0, F32), {}))
    registry._TERMS = dict(reversed(list(registry._TERMS.items())))
    assert bits(registry.collect(inter, BATCH, ce)[0]) \
        == bits(want + late(None, None, None)[0])


# ------------- (D) a model's counter and term cost one file under models/

THROWAWAY = '''
import flax.linen as nn
import jax.numpy as jnp

from dlrover_wuqiong_tpu.models import stack
from dlrover_wuqiong_tpu.models.sown import counters, sown, term


class Thrown(nn.Module):
    """An embedding, a head, and two sown values."""

    @nn.compact
    def __call__(self, idx):
        x = nn.Embed(32, 8, name="embed")(idx)
        self.sow("intermediates", "thrown_norm", jnp.abs(x).mean())
        self.sow("intermediates", "thrown_penalty", jnp.square(x).mean())
        return nn.Dense(32, name="head")(x)

    def init_params(self, rng, batch=1, seq=8):
        return stack.init_params(self, rng, batch, seq)


@counters
def thrown_stats(intermediates):
    norms = list(sown(intermediates, "thrown_norm"))
    return {"thrown_norm": jnp.stack(norms).mean()} if norms else {}


@term
def thrown_term(intermediates, batch, ce):
    sums = list(sown(intermediates, "thrown_penalty"))
    if not sums:
        return None
    return 0.5 * sum(sums), {"thrown_penalty": sum(sums)}
'''


def test_a_new_models_counter_and_term_reach_the_step_from_its_own_file(
        registry):
    module = types.ModuleType("thrown_model")
    sys.modules[module.__name__] = module
    try:
        exec(THROWAWAY, module.__dict__)
        counted = {kind: len(getattr(registry, kind))
                   for kind in ("_COUNTERS", "_TERMS", "_STEPS")}
        exec(THROWAWAY, module.__dict__)  # a reload: each counts once
        assert counted == {kind: len(getattr(registry, kind))
                           for kind in ("_COUNTERS", "_TERMS", "_STEPS")}
        assert "thrown_model.thrown_term" in registry._TERMS

        model = module.Thrown()
        params = model.init_params(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0, 32)
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        loss_fn = make_lm_loss(model.apply)  # trainer/ as it stands
        loss, stats = loss_fn.with_stats(params, batch)
        assert set(stats) == {"thrown_norm", "thrown_penalty"}
        ce = gpt.cross_entropy_loss(model.apply({"params": params},
                                                batch["input_ids"]),
                                    batch["labels"])
        assert bits(loss) == bits(ce + 0.5 * stats["thrown_penalty"])
        assert float(stats["thrown_penalty"]) > 0
        # the term is a term: its gradient reaches the embedding
        grads = jax.grad(loss_fn)(params, batch)
        bare = jax.grad(lambda p: gpt.cross_entropy_loss(
            model.apply({"params": p}, batch["input_ids"]),
            batch["labels"]))(params)
        assert not np.allclose(grads["embed"]["embedding"],
                               bare["embed"]["embedding"])
    finally:
        del sys.modules[module.__name__]


def test_two_registrants_may_not_give_one_name(registry):
    registry.counters(lambda inter: {"moe_dropped": jnp.zeros(())})
    with pytest.raises(ValueError, match="moe_dropped"):
        registry.collect(REGISTRANTS["collect_moe_stats"][2], BATCH, CE)


def test_a_registered_objective_stands_where_the_cross_entropy_stood(
        registry):
    """`@objective`: None from every registrant keeps the next-token
    cross-entropy to the bit (the program every other model traced); one
    answer replaces it and the terms join it as they joined the
    cross-entropy."""
    import flax.linen as nn

    class Sower(nn.Module):
        @nn.compact
        def __call__(self, idx):
            x = nn.Embed(16, 8, name="embed")(idx)
            self.sow("intermediates", "own_target", idx)
            return nn.Dense(16, name="head")(x)

    model = Sower()
    batch = {"input_ids": BATCH["labels"], "labels": BATCH["labels"][:, ::-1]}
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"])["params"]
    loss_fn = make_lm_loss(model.apply)
    logits = model.apply({"params": params}, batch["input_ids"])
    assert bits(loss_fn(params, batch)) == bits(
        gpt.cross_entropy_loss(logits, batch["labels"]))

    @registry.objective
    def own_objective(intermediates, batch, logits):
        targets = list(registry.sown(intermediates, "own_target"))
        return gpt.cross_entropy_loss(logits, targets[0]) if targets \
            else None

    assert registry.objective_of({}, batch, logits) is None
    assert bits(make_lm_loss(model.apply)(params, batch)) == bits(
        gpt.cross_entropy_loss(logits, batch["input_ids"]))


# ----------------------------------------- (E) the arrow points one way

def _imports(path):
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            yield from ((node.module or "", a.name) for a in node.names)
        elif isinstance(node, ast.Import):
            yield from ((a.name, "") for a in node.names)


# parallel/pipeline.py's hand-written GPT and Llama stacks (ROADMAP D20):
# known, by name, so that the next one fails here
PIPELINE_STACKS = {("models.gpt", "Block"), ("models.llama", "LlamaBlock"),
                   ("models.llama", "rope_freqs"),
                   ("models.llama", "RMSNorm")}


@pytest.mark.parametrize("package", ["trainer", "parallel"])
def test_the_step_asks_models_for_the_loss_and_for_collect_alone(package):
    found = {}
    for path in sorted((PACKAGE / package).rglob("*.py")):
        text = path.read_text()
        for module, name in _imports(path):
            if re.search(r"(^|\.)models(\.|$)", module) or (
                    name == "models" and not module.strip(".")):
                found.setdefault(path.name, set()).add(
                    (module.replace("dlrover_wuqiong_tpu.", ""), name))
        # no collector of a model's, and no key of one, by name
        assert not re.search(r"collect_[a-z_]+", text), path
        assert package != "trainer" or "index_kl" not in text, path
    asks = {("models.gpt", "cross_entropy_loss"), ("models.sown", "collect")}
    # since PR 70 the step asks for a model's own objective first, and
    # through models/sown.py alone
    own = {("models.sown", "objective_of")}
    assert found == {"trainer": {"train_step.py": asks | own},
                     "parallel": {"pipeline.py": asks | PIPELINE_STACKS}
                     }[package]


def test_sown_imports_nothing_of_the_package_and_no_model_keeps_a_copy():
    path = PACKAGE / "models" / "sown.py"
    assert {module for module, _ in _imports(path)} == {"__future__", "jax"}
    assert len(path.read_text().splitlines()) < 140  # four registrations
    for path in sorted((PACKAGE / "models").glob("*.py")):
        assert "_sown" not in path.read_text(), path
        if path.name != "sown.py":
            assert not any(
                isinstance(node, ast.FunctionDef) and node.name == "sown"
                for node in ast.walk(ast.parse(path.read_text()))), path
