"""models/stack.py, the one definition of the decoder stack: (a) the six
model classes built over it lower, compile and initialise to what they
did before it existed; (b) an architecture the repo does not have is a
config and a block, and the rest of the system takes it as it stands.
"""

import collections
import dataclasses
import hashlib
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest


from dlrover_wuqiong_tpu.analysis.hlo_scopes import scope_table
from dlrover_wuqiong_tpu.models.gpt import cross_entropy_loss


def _sha(obj) -> str:
    text = obj if isinstance(obj, str) else repr(obj)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _model(module: str, cls: str, config: str, **sizes):
    """The class at `nano()`, every block rematerialised."""
    import importlib

    mod = importlib.import_module(f"dlrover_wuqiong_tpu.models.{module}")
    cfg = dataclasses.replace(getattr(mod, config).nano(), remat=True,
                              **sizes)
    return getattr(mod, cls)(cfg)


def _digests(module: str, cls: str, config: str):
    """sha256[:16] of (lowered loss-and-gradient text, the compiled
    text's scope multiset, the parameter tree) at `nano()`, every block
    rematerialised."""
    model = _model(module, cls, config)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, 32), jnp.int32)

    def loss(p, ids):
        return cross_entropy_loss(model.apply({"params": p}, ids), ids)

    low = jax.jit(jax.value_and_grad(loss)).lower(params, ids)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    return (
        _sha(low.as_text()),
        _sha(sorted(collections.Counter(
            scope_table(low.compile().as_text()).values()).items())),
        _sha(sorted((jax.tree_util.keystr(path), leaf.shape, str(leaf.dtype))
                    for path, leaf in leaves)))


# sha256[:16] of the lowered text, of the compiled step's scopes and of
# the parameter tree, TAKEN ON THE PARENT CHECKOUT (commit 7b8b50a, PR
# 42, where every class carried its own stack) by running this very test
# there; the three classes with an expert layer: lowered text and scopes
# taken again at PR 45, which changed that layer's bookkeeping (counts by
# compare-and-sum, numbers carried by the sorts, gates by a select) and
# nothing of the stack — the tree digests are still the parent's
@pytest.mark.parametrize("module,cls,config,lowered,scopes,tree", [
    ("gpt", "GPT", "GPTConfig",
     "24fd6d1cf63c07f1", "40abb28f8dd32a24", "f1fae54484747283"),
    ("llama", "Llama", "LlamaConfig",
     "649d81779f575020", "98e8a743c043f778", "6a16c51b91691796"),
    ("nemotron_h", "NemotronH", "NemotronHConfig",
     "4c2a16b5a144e610", "84b6cee8ac1fddf2", "7f0485126678b172"),
    ("granite_hybrid", "GraniteHybrid", "GraniteHybridConfig",
     "3a7dd6b94e30cd6b", "dc402dd7761a3ef5", "04a698d8d92be19b"),
    ("smallthinker", "SmallThinker", "SmallThinkerConfig",
     "062cfcbe6c9fbe94", "a795e3de342fdead", "8f75874dcedf37fa"),
    ("latent_moe", "LatentMoE", "LatentMoEConfig",
     "c4ce8d7db7b3e5cb", "866990afd005a340", "83d62fd421b25480"),
])
def test_a_model_over_the_stack_is_the_program_it_was(
        module, cls, config, lowered, scopes, tree):
    """The stack moved, the program did not: with every block
    rematerialised, the loss and gradient lower to the parent's text
    (so the remat wrapper, the layer loop, the head and their order are
    the parent's op for op), the compiled text's ops fall under the
    parent's scopes as often (what `benchmark/`'s `*.scopes.json` files
    and `analysis/hlo_scopes.py` read), and `init_params` gives the
    parent's paths, shapes and dtypes (what checkpoints,
    `parallel/sharding.py`'s rules and `untrained_params` bind to)."""
    assert _digests(module, cls, config) == (lowered, scopes, tree)


def test_a_rematerialised_block_branches_on_its_static_arguments():
    """`static_argnums`: GPT's `deterministic` is a Python bool its
    dropout branches on.  Left among the recomputed call's traced
    arguments (the parent's wrapper) it came back as a tracer, and a GPT
    with dropout AND remat could not be traced at all."""
    from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig

    model = GPT(dataclasses.replace(GPTConfig.nano(), n_layer=1, dropout=0.5,
                                    remat=True, dtype=jnp.float32))
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)

    @jax.jit
    def both(params):
        return (model.apply({"params": params}, ids),
                model.apply({"params": params}, ids, False,
                            rngs={"dropout": jax.random.PRNGKey(1)}))

    still, noisy = both(params)
    assert np.isfinite(noisy).all() and not np.allclose(still, noisy)


# ------------------------------------------- the mesh hand-off (ROADMAP D19)


def _kernels(jaxpr, sharded=False):
    """{(name of a pallas_call under `jaxpr`, whether it sits inside a
    shard_map)}: one outside is GSPMD's to partition, which it cannot."""
    found = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.add((eqn.params["name"], sharded))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= _kernels(sub, sharded
                              or eqn.primitive.name == "shard_map")
    return found


def _kernel_names(model, seq):
    """The kernels the model's loss and gradient trace at 2 x seq."""
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    return _kernels(jax.make_jaxpr(jax.grad(
        lambda p, ids: cross_entropy_loss(
            model.apply({"params": p}, ids), ids)))(params, ids).jaxpr)


# widths at which the scan (`ops/ssd.scan_route`) and a share of an expert
# layer (`ops/grouped_matmul.experts_route`) take their kernels on one TPU
# device: `nano()` is too small to take them anywhere
_SCAN = dict(mamba_heads=8, mamba_head_dim=64, n_groups=2, state_size=128,
             chunk_size=128, max_seq_len=256)
_SHARE = dict(num_experts=8, experts_held=4, expert_width=128)
MESHED = {
    "NemotronH": ("nemotron_h", "NemotronHConfig", {**_SCAN, **_SHARE},
                  {"dwt_ssd_fwd", "dwt_gmm"}),
    "GraniteHybrid": ("granite_hybrid", "GraniteHybridConfig", _SCAN,
                      {"dwt_ssd_fwd"}),
    "SmallThinker": ("smallthinker", "SmallThinkerConfig",
                     {**_SHARE, "max_seq_len": 256}, {"dwt_gmm"}),
}


@pytest.mark.parametrize("cls", sorted(MESHED))
def test_a_config_that_declares_a_mesh_is_handed_the_plans(monkeypatch, cls):
    """On more than one device `auto_accelerate` hands `mesh=` to every
    model config that DECLARES the field, whatever else it declares (the
    parent asked for `attn_impl`, which these three do not have): with
    the backend patched to read "TPU" the model as given traces its scan
    and grouped kernels, the model that comes back traces none outside a
    shard_map (a Mosaic kernel there is a program GSPMD cannot partition;
    its attention's sit in `attend`'s own), and an optimizer step runs
    on the two devices."""
    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.ops import mosaic

    module, config, sizes, kernels = MESHED[cls]
    model = _model(module, cls, config, **sizes)
    res = auto_accelerate(model, strategy=[("fsdp", {})],
                          devices=jax.devices()[:2],
                          optimizer=optax.adamw(1e-3), seq_len=256)
    assert res.mesh.size == 2 and res.model.config.mesh is res.mesh
    with monkeypatch.context() as mp:
        mp.setattr(mosaic, "on_tpu", lambda: True)
        assert {(name, False) for name in kernels} <= _kernel_names(
            model, 256)
        assert all(name.startswith("dwt_fa_") and sharded
                   for name, sharded in _kernel_names(res.model, 256))
    ids = np.random.default_rng(0).integers(0, 256, (2, 256), dtype=np.int32)
    batch = res.place_batch({"input_ids": ids, "labels": ids})
    state, metrics = res.train_step(res.state, batch)
    assert np.isfinite(float(metrics["loss"])) and int(state.step) == 1


def test_the_latent_stack_on_a_mesh_is_refused_at_build_time():
    """`LatentMoEConfig` declares `mesh` too: its attention (q and k wider
    than v) runs on one device only, and `models/attention.attend` says so
    while `auto_accelerate` draws the parameters — not later, as a Mosaic
    kernel GSPMD cannot split."""
    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.models.latent_moe import (
        LatentMoE, LatentMoEConfig)

    with pytest.raises(ValueError, match="latent attention.*one device"):
        auto_accelerate(LatentMoE(LatentMoEConfig.nano()),
                        strategy=[("fsdp", {})], devices=jax.devices()[:2],
                        optimizer=optax.adamw(1e-3))


# ------------------------- the next architecture is a config and a block


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """An architecture the repo does not have: attention and SwiGLU read
    ONE norm's output and are added to the stream together."""
    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 96
    num_layers: int = 2
    num_heads: int = 4
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    mesh: Any = None

    def llama(self):
        from dlrover_wuqiong_tpu.models.llama import LlamaConfig

        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_layers=self.num_layers, num_heads=self.num_heads,
            num_kv_heads=self.num_heads, max_seq_len=64,
            rms_eps=self.rms_eps, dtype=self.dtype, mesh=self.mesh)


class ParallelBlock(nn.Module):
    config: ParallelConfig

    @nn.compact
    def __call__(self, x, cos, sin):
        from jax.ad_checkpoint import checkpoint_name

        from dlrover_wuqiong_tpu.models.llama import (
            LlamaAttention, LlamaMLP, RMSNorm)
        from dlrover_wuqiong_tpu.parallel.sharding import pin_activation

        cfg = self.config
        x = pin_activation(x, cfg.mesh)
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="input_norm")(x)
        attn = LlamaAttention(cfg.llama(), name="attention")(h, cos, sin)
        mlp = LlamaMLP(cfg.llama(), name="feed_forward")(h)
        return x + checkpoint_name(attn, "attn_out") \
            + checkpoint_name(mlp, "mlp_out")


class ParallelLM(nn.Module):
    config: ParallelConfig

    @nn.compact
    def __call__(self, idx):
        from dlrover_wuqiong_tpu.models import stack
        from dlrover_wuqiong_tpu.models.llama import RMSNorm, rope_freqs

        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="embed_tokens")(idx)
        cos, sin = rope_freqs(cfg.hidden_size // cfg.num_heads, 64, 1e4)
        x = stack.layers(ParallelBlock, cfg, [()] * cfg.num_layers, x,
                         cos, sin)
        return stack.untied_head(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="norm")(x),
            cfg.vocab_size, cfg.dtype)

    def init_params(self, rng, batch: int = 1, seq: int = 8):
        from dlrover_wuqiong_tpu.models import stack

        return stack.init_params(self, rng, batch, seq)


def test_a_new_architecture_is_a_config_a_block_and_a_five_line_module():
    """What the stack is for: `ParallelLM` above brings a config, a block
    and the lines that name them, and nothing else in the repo is edited
    for it.  `auto_accelerate` shards its `layers_<i>` leaves over `fsdp`
    by `parallel/sharding.py`'s rules as they stand, hands it the mesh,
    and runs an optimizer step on two devices with every block
    rematerialised; and the rematerialised gradient is the plain one."""
    from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
    from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

    res = auto_accelerate(ParallelLM(ParallelConfig()),
                          strategy=[("fsdp", {})], devices=jax.devices()[:2],
                          optimizer=optax.adamw(1e-3), seq_len=32)
    assert res.model.config.mesh is res.mesh
    specs = {jax.tree_util.keystr(path): sh.spec for path, sh in
             jax.tree_util.tree_flatten_with_path(
                 res.state_shardings.params)[0]}
    layer = {k: v for k, v in specs.items() if "layers_1" in k}
    assert len(layer) == 8
    assert all("fsdp" in spec for key, spec in layer.items()
               if key.endswith("['kernel']")), layer
    assert "fsdp" in specs["['lm_head']['kernel']"]

    ids = np.random.default_rng(0).integers(0, 256, (4, 32), dtype=np.int32)
    batch = {"input_ids": ids, "labels": ids}
    params = jax.device_get(res.state.params)
    state, metrics = res.train_step(res.state, res.place_batch(batch))
    assert np.isfinite(float(metrics["loss"])) and int(state.step) == 1

    def grad_fn(remat):
        model = ParallelLM(ParallelConfig(remat=remat))
        return jax.jit(jax.grad(make_lm_loss(model.apply)))

    # the barrier that keeps XLA from merging the recomputed forward away
    barrier = "optimization_barrier"
    assert barrier in grad_fn(True).lower(params, batch).as_text()
    assert barrier not in grad_fn(False).lower(params, batch).as_text()
    for got, want in zip(jax.tree.leaves(grad_fn(True)(params, batch)),
                         jax.tree.leaves(grad_fn(False)(params, batch))):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


# ------------------------------- blocks that hand something on beside x


class HandingBlock(nn.Module):
    """Layer 0 hands on `m`, a projection of its input; every later
    layer adds `m` times its own matrix to the stream, and a shared
    array rides behind `handed` as it rides behind x elsewhere."""
    config: ParallelConfig
    layer: int

    @nn.compact
    def __call__(self, x, handed, shift):
        dense = nn.Dense(x.shape[-1], use_bias=False, dtype=x.dtype,
                         name="proj")
        if self.layer == 0:
            m = jnp.tanh(dense(x))
            return x + m, {**handed, "m": m}
        return x + dense(handed["m"] + shift), handed


class HandingLM(nn.Module):
    config: ParallelConfig

    @nn.compact
    def __call__(self, x, shift):
        from dlrover_wuqiong_tpu.models import stack

        return stack.layers(HandingBlock, self.config,
                            [(i,) for i in range(self.config.num_layers)],
                            x, shift, handed={})


@pytest.mark.parametrize("layers", [2, 3])
def test_a_block_hands_values_on_beside_x_under_remat(layers):
    """`handed`: what layer 0 makes, every later layer reads; each
    reader's cotangent flows back into it (with three layers the sum of
    TWO), every block is still recomputed in the backward pass (one
    barrier a block) and named `layers_<i>`, and the rematerialised
    gradient is the plain one — which is the gradient of the same lines
    written without the stack."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    shift = jnp.float32(0.25)

    def model(remat):
        return HandingLM(ParallelConfig(num_layers=layers, remat=remat))

    params = model(True).init(jax.random.PRNGKey(1), x, shift)["params"]
    assert sorted(params) == [f"layers_{i}" for i in range(layers)]

    def loss(remat):
        return lambda p, x: jnp.sum(jnp.sin(
            model(remat).apply({"params": p}, x, shift)))

    def by_hand(p, x):
        w = [p[f"layers_{i}"]["proj"]["kernel"] for i in range(layers)]
        m = jnp.tanh(x @ w[0])
        out = x + m
        for w_i in w[1:]:
            out = out + (m + shift) @ w_i
        return jnp.sum(jnp.sin(out))

    lowered = jax.jit(jax.grad(loss(True))).lower(params, x).as_text()
    assert lowered.count("optimization_barrier") >= layers
    assert "optimization_barrier" not in jax.jit(
        jax.grad(loss(False))).lower(params, x).as_text()
    want = jax.grad(by_hand, argnums=(0, 1))(params, x)
    for remat in (True, False):
        got = jax.grad(loss(remat), argnums=(0, 1))(params, x)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_a_model_without_handed_calls_its_blocks_as_it_did():
    """`handed=None` (every model but the decoder-hybrid-decoder): a
    block is called `(x, *shared)` and returns x alone — the pinned
    digests above hold the six classes' programs to what they were."""
    ids = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    model = ParallelLM(ParallelConfig())
    params = model.init_params(jax.random.PRNGKey(0))
    assert model.apply({"params": params}, ids).shape == (2, 8, 256)
