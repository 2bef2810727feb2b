"""Pipeline parallelism tests: GPipe schedule over the pp mesh axis.

Mirrors reference `atorch/atorch/tests` pipe tests in spirit — numerics of
the staged execution must match the dense model, and training must step.
Runs on the virtual 8-device CPU mesh (conftest).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest


from dlrover_wuqiong_tpu.auto.accelerate import auto_accelerate
from dlrover_wuqiong_tpu.models.gpt import GPT, GPTConfig
from dlrover_wuqiong_tpu.models.llama import Llama, LlamaConfig
from dlrover_wuqiong_tpu.parallel.mesh import MeshPlan, build_mesh
from dlrover_wuqiong_tpu.parallel.pipeline import (
    PipelinedLM,
    circular_layer_order,
    pipeline_1f1b,
    pipeline_apply,
    schedule_ticks,
    split_layer_params,
    stack_layer_params,
)


def _pp_mesh(pp=2, fsdp=1, tp=1):
    n = pp * fsdp * tp
    return build_mesh(MeshPlan(pp=pp, fsdp=fsdp, tp=tp), jax.devices()[:n])


class TestPipelineApply:
    def test_matches_sequential_scan(self):
        """The staged pipeline must be numerically identical to running the
        stacked layers sequentially."""
        mesh = _pp_mesh(pp=4)
        L, B, T, C = 4, 8, 16, 32
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (L, C, C), jnp.float32) * 0.1
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, C))

        def block(pl, h):
            return jnp.tanh(h @ pl)

        def seq(w, x):
            for i in range(L):
                x = block(w[i], x)
            return x

        with mesh:
            got = jax.jit(
                lambda w, x: pipeline_apply(block, w, x, mesh, 4))(w, x)
        want = seq(w, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_grads_match_sequential(self):
        mesh = _pp_mesh(pp=2)
        L, B, T, C = 2, 4, 8, 16
        w = jax.random.normal(jax.random.PRNGKey(0), (L, C, C)) * 0.1
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, C))

        def block(pl, h):
            return jnp.tanh(h @ pl)

        def loss_pp(w):
            with mesh:
                return pipeline_apply(block, w, x, mesh, 2).sum()

        def loss_seq(w):
            h = x
            for i in range(L):
                h = block(w[i], h)
            return h.sum()

        g_pp = jax.jit(jax.grad(loss_pp))(w)
        g_seq = jax.grad(loss_seq)(w)
        np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq),
                                   atol=1e-4)


class TestInterleavedSchedule:
    """Circular virtual-stage schedule (Megatron interleaved 1F1B's bubble
    reduction, ref StageInterleaver.py)."""

    def _toy(self, L=8, B=8, T=4, C=16):
        w = jax.random.normal(jax.random.PRNGKey(0), (L, C, C)) * 0.1
        x = jax.random.normal(jax.random.PRNGKey(1), (B, T, C))

        def block(pl, h):
            return jnp.tanh(h @ pl)

        def seq(w, x):
            for i in range(L):
                x = block(w[i], x)
            return x

        return w, x, block, seq

    def test_matches_sequential(self):
        mesh = _pp_mesh(pp=2)
        w, x, block, seq = self._toy()
        order = circular_layer_order(8, pp=2, v=2)
        with mesh:
            got = jax.jit(lambda w, x: pipeline_apply(
                block, w, x, mesh, 4, schedule="interleaved",
                virtual_stages=2))(w[jnp.array(order)], x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(seq(w, x)),
                                   atol=1e-5)

    def test_grads_match_sequential(self):
        mesh = _pp_mesh(pp=2)
        w, x, block, seq = self._toy()
        order = jnp.array(circular_layer_order(8, pp=2, v=2))

        def loss_ppl(w):
            with mesh:
                return pipeline_apply(block, w[order], x, mesh, 4,
                                      schedule="interleaved",
                                      virtual_stages=2).sum()

        g_ppl = jax.jit(jax.grad(loss_ppl))(w)
        g_seq = jax.grad(lambda w: seq(w, x).sum())(w)
        np.testing.assert_allclose(np.asarray(g_ppl), np.asarray(g_seq),
                                   atol=1e-4)

    def test_bubble_smaller_than_gpipe(self):
        """At m=4, s=4, v=2 the interleaved bubble must beat GPipe's."""
        _, gpipe = schedule_ticks("gpipe", 4, 4)
        _, inter = schedule_ticks("interleaved", 4, 4, virtual_stages=2)
        assert inter < gpipe
        assert gpipe == pytest.approx(3 / 7)
        assert inter == pytest.approx(3 / 11)

    def test_rejects_bad_microbatches(self):
        mesh = _pp_mesh(pp=2)
        w, x, block, _ = self._toy()
        with pytest.raises(ValueError, match="divisible"):
            with mesh:
                pipeline_apply(block, w, x, mesh, 3,
                               schedule="interleaved", virtual_stages=2)


class TestOneFOneB:
    """Manual 1F1B schedule: numerics + O(pp) stash."""

    def _setup(self, pp, L=4, M=4, B=8, T=4, C=16):
        mesh = _pp_mesh(pp=pp)
        w = jax.random.normal(jax.random.PRNGKey(0), (L, C, C)) * 0.1
        hp = {"w": jax.random.normal(jax.random.PRNGKey(1), (C,)) * 0.1}
        x = jax.random.normal(jax.random.PRNGKey(2), (M, B // M, T, C))
        tgt = jax.random.normal(jax.random.PRNGKey(3), (M, B // M, T))

        def block(pl, h):
            return jnp.tanh(h @ pl)

        def head_loss(hp, h, t):
            return jnp.mean((h @ hp["w"] - t) ** 2)

        return mesh, w, hp, x, tgt, block, head_loss

    def _reference(self, w, hp, x, tgt, block, head_loss):
        """Plain autodiff over the sequential model."""
        def total(w, hp, x):
            def one(mx, mt):
                h = mx
                for i in range(w.shape[0]):
                    h = block(w[i], h)
                return head_loss(hp, h, mt)
            return jnp.mean(jax.vmap(one)(x, tgt))

        loss, grads = jax.value_and_grad(total, argnums=(0, 1, 2))(w, hp, x)
        return loss, grads

    @pytest.mark.parametrize("pp", [2, 4])
    def test_matches_autodiff(self, pp):
        mesh, w, hp, x, tgt, block, head_loss = self._setup(pp)
        with mesh:
            loss, d_w, d_hp, d_x = jax.jit(
                lambda w, hp, x, tgt: pipeline_1f1b(
                    block, head_loss, w, hp, x, tgt, mesh))(w, hp, x, tgt)
        ref_loss, (rd_w, rd_hp, rd_x) = self._reference(
            w, hp, x, tgt, block, head_loss)
        np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-5)
        np.testing.assert_allclose(np.asarray(d_w), np.asarray(rd_w),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(d_hp["w"]),
                                   np.asarray(rd_hp["w"]), atol=1e-4)
        np.testing.assert_allclose(np.asarray(d_x), np.asarray(rd_x),
                                   atol=1e-4)

    def test_pp1_path_matches(self):
        mesh, w, hp, x, tgt, block, head_loss = self._setup(pp=2)
        mesh1 = _pp_mesh(pp=1)
        loss, d_w, d_hp, d_x = pipeline_1f1b(block, head_loss, w, hp, x,
                                             tgt, mesh1)
        ref_loss, (rd_w, _, _) = self._reference(w, hp, x, tgt, block,
                                                 head_loss)
        np.testing.assert_allclose(float(loss), float(ref_loss), atol=1e-6)
        np.testing.assert_allclose(np.asarray(d_w), np.asarray(rd_w),
                                   atol=1e-5)

    def test_gpt_value_and_grad_matches_dense(self):
        """PipelinedLM.value_and_grad (1f1b) vs autodiff on the dense GPT —
        including the tied-wte grad that sums embed+head contributions."""
        from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

        cfg = dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                  remat=False, use_flash_attention=False)
        mesh = _pp_mesh(pp=2)
        model = GPT(cfg)
        dense_params = model.init_params(jax.random.PRNGKey(0))
        plm = PipelinedLM(model, mesh, num_microbatches=2, schedule="1f1b")
        pp_params = plm.from_flat_params(dense_params)
        data = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0,
                                  cfg.vocab_size)
        batch = {"input_ids": data[:, :-1], "labels": data[:, 1:]}
        with mesh:
            loss, grads = jax.jit(plm.value_and_grad)(pp_params, batch)
        dense_loss, dense_grads = jax.value_and_grad(
            make_lm_loss(model.apply))(dense_params, batch)
        np.testing.assert_allclose(float(loss), float(dense_loss),
                                   atol=2e-4)
        flat = plm.to_flat_params(grads)
        for k in ("wte", "wpe", "ln_f"):
            np.testing.assert_allclose(
                np.asarray(jax.tree.leaves(flat[k])[0]),
                np.asarray(jax.tree.leaves(dense_grads[k])[0]), atol=5e-3)

    def test_1f1b_compiled_memory_below_gpipe(self):
        """The O(pp) stash must show up as lower temp memory than GPipe's
        O(M) residuals when M >> pp (compiled on the CPU mesh)."""
        pp, L, M, B, T, C = 2, 4, 16, 32, 8, 64
        mesh, w, hp, x, tgt, block, head_loss = self._setup(
            pp=pp, L=L, M=M, B=B, T=T, C=C)

        def loss_gpipe(w, hp, x, tgt):
            with mesh:
                xf = x.reshape(B, T, C)
                y = pipeline_apply(block, w, xf, mesh, M)
                ym = y.reshape(M, B // M, T, C)
                return jnp.mean(jax.vmap(
                    lambda h, t: head_loss(hp, h, t))(ym, tgt))

        def grads_1f1b(w, hp, x, tgt):
            with mesh:
                return pipeline_1f1b(block, head_loss, w, hp, x, tgt, mesh)

        gpipe_c = jax.jit(jax.grad(loss_gpipe, argnums=(0, 1, 2))).lower(
            w, hp, x, tgt).compile()
        f1b_c = jax.jit(grads_1f1b).lower(w, hp, x, tgt).compile()
        try:
            gp_tmp = gpipe_c.memory_analysis().temp_size_in_bytes
            fb_tmp = f1b_c.memory_analysis().temp_size_in_bytes
        except (AttributeError, NotImplementedError):
            pytest.skip("backend has no memory_analysis")
        assert fb_tmp < gp_tmp, (fb_tmp, gp_tmp)


class TestPipelinedLM:
    def _gpt_cfg(self):
        return dataclasses.replace(GPTConfig.nano(), dtype=jnp.float32,
                                   remat=False, use_flash_attention=False)

    def test_gpt_logits_match_dense(self):
        cfg = self._gpt_cfg()
        mesh = _pp_mesh(pp=2)
        model = GPT(cfg)
        dense_params = model.init_params(jax.random.PRNGKey(0))
        plm = PipelinedLM(model, mesh, num_microbatches=2)
        pp_params = plm.init_params(jax.random.PRNGKey(0))
        # restructure dense params into the pipelined layout for comparison
        non_layer, layers, _ = split_layer_params(dict(dense_params))
        pp_from_dense = dict(non_layer, blocks=stack_layer_params(layers))

        idx = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                 cfg.vocab_size)
        with mesh:
            got = jax.jit(lambda p: plm.apply({"params": p}, idx))(
                pp_from_dense)
        want = model.apply({"params": dense_params}, idx)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=2e-4)
        # init layouts agree structurally
        assert jax.tree.structure(pp_params) == jax.tree.structure(
            pp_from_dense)

    def test_llama_logits_match_dense(self):
        cfg = dataclasses.replace(LlamaConfig.nano(), dtype=jnp.float32,
                                  remat=False, use_flash_attention=False)
        mesh = _pp_mesh(pp=2)
        model = Llama(cfg)
        dense_params = model.init_params(jax.random.PRNGKey(0))
        plm = PipelinedLM(model, mesh, num_microbatches=2)
        plm.init_params(jax.random.PRNGKey(0))
        non_layer, layers, _ = split_layer_params(dict(dense_params))
        pp_from_dense = dict(non_layer, blocks=stack_layer_params(layers))

        idx = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0,
                                 cfg.vocab_size)
        with mesh:
            got = jax.jit(lambda p: plm.apply({"params": p}, idx))(
                pp_from_dense)
        want = model.apply({"params": dense_params}, idx)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=2e-4)


class TestPipelineTraining:
    def test_auto_accelerate_pp_trains(self):
        """pp=2 x fsdp=2 end-to-end: loss decreases over steps."""
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  dtype=jnp.float32)
        res = auto_accelerate(
            GPT(cfg), optimizer=optax.adam(1e-2),
            strategy=[("pipeline_parallel", {"size": 2, "microbatches": 2}),
                      ("fsdp", {})],
            devices=jax.devices()[:4])
        data = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0,
                                  cfg.vocab_size)
        batch = res.place_batch({"input_ids": data[:, :-1],
                                 "labels": data[:, 1:]})
        state = res.state
        losses = []
        for _ in range(5):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]
        # stacked block params actually sharded over pp
        blocks_sh = res.state_shardings.params["blocks"]
        leaf = jax.tree.leaves(blocks_sh)[0]
        assert "pp" in str(leaf.spec)

    @pytest.mark.parametrize("schedule,vstages",
                             [("1f1b", 1), ("interleaved", 2)])
    def test_auto_accelerate_schedules_train(self, schedule, vstages):
        """pp=2 end-to-end under each non-default schedule: loss decreases
        and tp composition holds (tp=2 exercises GSPMD inside the stage)."""
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  n_layer=2 * vstages,
                                  dtype=jnp.float32)
        res = auto_accelerate(
            GPT(cfg), optimizer=optax.adam(1e-2),
            strategy=[("pipeline_parallel",
                       {"size": 2, "microbatches": 2,
                        "schedule": schedule, "virtual_stages": vstages}),
                      ("tensor_parallel", {"size": 2})],
            devices=jax.devices()[:4])
        data = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0,
                                  cfg.vocab_size)
        batch = res.place_batch({"input_ids": data[:, :-1],
                                 "labels": data[:, 1:]})
        state = res.state
        losses = []
        for _ in range(5):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    def test_generic_adapter_model_stages(self):
        """Arbitrary layer-stack models pipeline via the adapter hooks."""
        import flax.linen as nn

        class ToyCfg:
            n_layer = 2

        class ToyBlock(nn.Module):
            @nn.compact
            def __call__(self, h):
                return h + nn.Dense(h.shape[-1])(jnp.tanh(h))

        class Toy:
            """Minimal custom model: h_<i> blocks + in/out dense."""
            config = ToyCfg()

            def init_params(self, rng):
                C = 8
                ks = jax.random.split(rng, 4)
                p = {"inp": nn.Dense(C).init(
                    ks[0], jnp.zeros((1, 1, 4)))["params"],
                    "out": nn.Dense(3).init(
                        ks[1], jnp.zeros((1, 1, C)))["params"]}
                blk = ToyBlock()
                for i in range(2):
                    p[f"h_{i}"] = blk.init(
                        ks[2 + i], jnp.zeros((1, 1, C)))["params"]
                return p

            def apply(self, variables, x, deterministic=True, mutable=None):
                p = variables["params"]
                h = nn.Dense(8).apply({"params": p["inp"]}, x)
                for i in range(2):
                    h = ToyBlock().apply({"params": p[f"h_{i}"]}, h)
                return nn.Dense(3).apply({"params": p["out"]}, h)

        mesh = _pp_mesh(pp=2)
        toy = Toy()
        dense = toy.init_params(jax.random.PRNGKey(0))
        plm = PipelinedLM(
            toy, mesh, num_microbatches=2,
            embed_fn=lambda p, x: nn.Dense(8).apply(
                {"params": p["inp"]}, x),
            block_builder=lambda p, x, det: (
                lambda pl, h: ToyBlock().apply({"params": pl}, h)),
            head_fn=lambda p, h: nn.Dense(3).apply(
                {"params": p["out"]}, h),
            embed_keys=("inp",), head_keys=("out",))
        pp_params = plm.from_flat_params(dense)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 4))
        with mesh:
            got = jax.jit(lambda p: plm.apply({"params": p}, x))(pp_params)
        want = toy.apply({"params": dense}, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @pytest.mark.parametrize("schedule,vstages",
                             [("gpipe", 1), ("interleaved", 2)])
    def test_moe_through_pipeline(self, schedule, vstages):
        """MoE models pipeline: the router aux loss crosses the schedule
        as an explicit scalar and matches the dense model's."""
        from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

        cfg = dataclasses.replace(GPTConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  n_layer=2 * vstages, moe_experts=4,
                                  dtype=jnp.float32)
        mesh = _pp_mesh(pp=2)
        model = GPT(cfg)
        dense_params = model.init_params(jax.random.PRNGKey(0))
        plm = PipelinedLM(model, mesh, num_microbatches=2,
                          schedule=schedule, virtual_stages=vstages)
        pp_params = plm.from_flat_params(dense_params)
        data = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0,
                                  cfg.vocab_size)
        batch = {"input_ids": data[:, :-1], "labels": data[:, 1:]}
        with mesh:
            loss = jax.jit(make_lm_loss(plm.apply))(pp_params, batch)
        dense_loss = make_lm_loss(model.apply)(dense_params, batch)
        # router statistics (capacity drops, aux balance) are computed per
        # microbatch in a pipeline vs whole-batch densely — standard
        # microbatched-MoE semantics, so close but not bitwise equal
        np.testing.assert_allclose(float(loss), float(dense_loss),
                                   atol=2e-2)
        # the aux term is actually present (loss > plain ce)
        logits = model.apply({"params": dense_params},
                             batch["input_ids"])
        from dlrover_wuqiong_tpu.models.gpt import cross_entropy_loss

        ce = float(cross_entropy_loss(logits, batch["labels"]))
        assert float(loss) > ce

    def test_moe_pipeline_trains_e2e(self):
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  moe_experts=4, dtype=jnp.float32)
        res = auto_accelerate(
            GPT(cfg), optimizer=optax.adam(1e-2),
            strategy=[("pipeline_parallel", {"size": 2,
                                             "microbatches": 2}),
                      ("fsdp", {})],
            devices=jax.devices()[:4])
        data = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0,
                                  cfg.vocab_size)
        batch = res.place_batch({"input_ids": data[:, :-1],
                                 "labels": data[:, 1:]})
        state, losses = res.state, []
        for _ in range(5):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    @pytest.mark.parametrize("schedule,vstages",
                             [("1f1b", 1), ("interleaved", 2)])
    def test_schedules_compose_with_grad_accum(self, schedule, vstages):
        """Outer grad-accum microbatches wrap the pipeline's inner ones."""
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  n_layer=2 * vstages, dtype=jnp.float32)
        res = auto_accelerate(
            GPT(cfg), optimizer=optax.adam(1e-2),
            strategy=[("pipeline_parallel",
                       {"size": 2, "microbatches": 2,
                        "schedule": schedule, "virtual_stages": vstages}),
                      ("fsdp", {}), ("grad_accum", {"steps": 2})],
            devices=jax.devices()[:4])
        data = jax.random.randint(jax.random.PRNGKey(0), (2, 8, 33), 0,
                                  cfg.vocab_size)
        batch = res.place_batch({"input_ids": data[..., :-1],
                                 "labels": data[..., 1:]})
        state, losses = res.state, []
        for _ in range(4):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    def test_pp_sp_gspmd_composes(self):
        """Sequence parallel in gspmd mode (XLA-inserted collectives)
        composes with the pipeline — only ring/ulysses are rejected."""
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  dtype=jnp.float32)
        res = auto_accelerate(
            GPT(cfg), optimizer=optax.adam(1e-2),
            strategy=[("pipeline_parallel", {"size": 2,
                                             "microbatches": 2}),
                      ("sequence_parallel", {"size": 2, "impl": "gspmd"}),
                      ("fsdp", {})],
            devices=jax.devices()[:8])
        data = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0,
                                  cfg.vocab_size)
        batch = res.place_batch({"input_ids": data[:, :-1],
                                 "labels": data[:, 1:]})
        state, losses = res.state, []
        for _ in range(4):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    @pytest.mark.parametrize("impl", ["ring", "ulysses"])
    def test_pp_sp_ring_ulysses_grads_match_plain_pp(self, impl):
        """pp x ring/ulysses SP (round-4 closure): the attention shard_map
        nests inside the pipeline's manual-pp body (context AbstractMesh +
        VMA tracking), and the gradients must equal plain-pp's — this
        exact check caught the check_vma=False transpose corruption."""
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  dtype=jnp.float32)
        data = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0,
                                  cfg.vocab_size)

        def grads_of(strategy):
            res = auto_accelerate(GPT(cfg), optimizer=optax.sgd(0.0),
                                  strategy=strategy,
                                  devices=jax.devices()[:8],
                                  rng=jax.random.PRNGKey(5))
            batch = res.place_batch({"input_ids": data[:, :-1],
                                     "labels": data[:, 1:]})
            g = jax.jit(jax.grad(lambda p: res.loss_fn(p, batch)))(
                dict(res.state.params))
            return jax.tree.map(np.asarray, g)

        pp = [("pipeline_parallel", {"size": 2, "microbatches": 2})]
        base = grads_of(pp + [("fsdp", {})])
        sp = grads_of(pp + [("sequence_parallel",
                             {"size": 2, "impl": impl}), ("fsdp", {})])
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                    atol=1e-6),
            base, sp)

    def test_1f1b_ring_sp_grads_match_and_train(self):
        """ring-SP inside the MANUAL 1f1b backward: gradient-exact vs
        plain 1f1b, and training steps."""
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  dtype=jnp.float32)
        data = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0,
                                  cfg.vocab_size)

        def vg_of(strategy):
            res = auto_accelerate(GPT(cfg), optimizer=optax.adam(1e-2),
                                  strategy=strategy,
                                  devices=jax.devices()[:8],
                                  rng=jax.random.PRNGKey(5))
            batch = res.place_batch({"input_ids": data[:, :-1],
                                     "labels": data[:, 1:]})
            loss, g = jax.jit(res.model.value_and_grad)(
                dict(res.state.params), batch)
            return res, batch, float(loss), jax.tree.map(np.asarray, g)

        pp = [("pipeline_parallel", {"size": 2, "microbatches": 2,
                                     "schedule": "1f1b"})]
        _, _, l0, g0 = vg_of(pp + [("fsdp", {})])
        res, batch, l1, g1 = vg_of(
            pp + [("sequence_parallel", {"size": 2, "impl": "ring"}),
                  ("fsdp", {})])
        assert abs(l0 - l1) < 1e-5
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                    atol=1e-6),
            g0, g1)
        state, losses = res.state, []
        for _ in range(4):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    def test_llama_trains_under_1f1b(self):
        """The 1f1b value_and_grad path handles the Llama family (untied
        embed/head key split) too."""
        cfg = dataclasses.replace(LlamaConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  dtype=jnp.float32)
        res = auto_accelerate(
            Llama(cfg), optimizer=optax.adam(1e-2),
            strategy=[("pipeline_parallel",
                       {"size": 2, "microbatches": 2,
                        "schedule": "1f1b"}), ("fsdp", {})],
            devices=jax.devices()[:4])
        data = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                  cfg.vocab_size)
        batch = res.place_batch({"input_ids": data[:, :-1],
                                 "labels": data[:, 1:]})
        state, losses = res.state, []
        for _ in range(4):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    def test_moe_pp_ep_composes(self):
        """Expert parallelism composes with the pipeline: experts shard
        over ep inside the stage while layers shard over pp."""
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  moe_experts=4, dtype=jnp.float32)
        res = auto_accelerate(
            GPT(cfg), optimizer=optax.adam(1e-2),
            strategy=[("pipeline_parallel", {"size": 2,
                                             "microbatches": 2}),
                      ("expert_parallel", {"size": 2}), ("fsdp", {})],
            devices=jax.devices()[:8])
        data = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0,
                                  cfg.vocab_size)
        batch = res.place_batch({"input_ids": data[:, :-1],
                                 "labels": data[:, 1:]})
        state, losses = res.state, []
        for _ in range(4):
            state, m = res.train_step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    def test_local_sgd_pp_rejected_clearly(self):
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False)
        with pytest.raises(ValueError, match="local_sgd.*pipeline"):
            auto_accelerate(
                GPT(cfg),
                strategy=[("pipeline_parallel", {"size": 2}),
                          ("data_parallel", {"size": 2}),
                          ("local_sgd", {"sync_every": 2})],
                devices=jax.devices()[:4])

    def test_moe_1f1b_composes_and_matches_gpipe(self):
        """MoE x 1f1b (round-3 rejection, now closed): the manual backward
        seeds the router aux-loss cotangent (1/M per microbatch), so the
        1f1b loss equals gpipe's on identical init/batch and training
        makes progress."""
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  moe_experts=4, dtype=jnp.float32)
        data = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0,
                                  cfg.vocab_size)

        def build(schedule):
            res = auto_accelerate(
                GPT(cfg), optimizer=optax.adam(1e-2),
                strategy=[("pipeline_parallel",
                           {"size": 2, "microbatches": 2,
                            "schedule": schedule}), ("fsdp", {})],
                devices=jax.devices()[:4], rng=jax.random.PRNGKey(5))
            batch = res.place_batch({"input_ids": data[:, :-1],
                                     "labels": data[:, 1:]})
            return res, batch

        res_g, b_g = build("gpipe")
        res_f, b_f = build("1f1b")
        _, m_g = res_g.train_step(res_g.state, b_g)
        state, m_f = res_f.train_step(res_f.state, b_f)
        # same init, same batch, aux included on both paths
        assert abs(float(m_g["loss"]) - float(m_f["loss"])) < 1e-4, (
            float(m_g["loss"]), float(m_f["loss"]))
        losses = [float(m_f["loss"])]
        for _ in range(3):
            state, m_f = res_f.train_step(state, b_f)
            losses.append(float(m_f["loss"]))
        assert losses[-1] < losses[0], losses

    def test_pp_rejects_indivisible_layers(self):
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False)  # 2 layers
        with pytest.raises(ValueError, match="divisible"):
            auto_accelerate(GPT(cfg),
                            strategy=[("pipeline_parallel", {"size": 3})],
                            devices=jax.devices()[:3])


class TestOneFOneBCustomHeadLoss:
    """1f1b x custom loss (round-4 partial closure): a PER-MICROBATCH
    head loss — the shape the in-schedule backward can seed — threads
    through ('pipeline_parallel', {'head_loss': fn}); whole-batch
    loss_fn stays rejected with a message pointing here."""

    def test_label_smoothed_head_loss_matches_gpipe_equivalent(self):
        import flax.linen as nn

        cfg = dataclasses.replace(GPTConfig.nano(), remat=False,
                                  use_flash_attention=False,
                                  dtype=jnp.float32)
        data = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0,
                                  cfg.vocab_size)
        EPS = 0.1

        def smoothed_ce_from_logits(logits, labels):
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            nll = -jnp.take_along_axis(logp, labels[..., None],
                                       -1)[..., 0]
            uniform = -logp.mean(-1)
            return ((1 - EPS) * nll + EPS * uniform).mean()

        def head_loss(hp, h, labels):
            x = nn.LayerNorm(dtype=cfg.dtype).apply({"params": hp["ln_f"]},
                                                    h)
            logits = jnp.einsum("bte,ve->btv", x,
                                hp["wte"]["embedding"].astype(cfg.dtype))
            return smoothed_ce_from_logits(logits, labels)

        res = auto_accelerate(
            GPT(cfg), optimizer=optax.sgd(0.0),
            strategy=[("pipeline_parallel",
                       {"size": 2, "microbatches": 2, "schedule": "1f1b",
                        "head_loss": head_loss}), ("fsdp", {})],
            devices=jax.devices()[:8], rng=jax.random.PRNGKey(5))
        batch = res.place_batch({"input_ids": data[:, :-1],
                                 "labels": data[:, 1:]})
        loss_1f1b, g_1f1b = jax.jit(res.model.value_and_grad)(
            dict(res.state.params), batch)

        # gpipe equivalent: whole-batch custom loss over the same model
        def whole_batch_loss(params, batch):
            logits = res_g.model.apply({"params": params},
                                       batch["input_ids"])
            return smoothed_ce_from_logits(logits, batch["labels"])

        res_g = auto_accelerate(
            GPT(cfg), optimizer=optax.sgd(0.0),
            strategy=[("pipeline_parallel",
                       {"size": 2, "microbatches": 2}), ("fsdp", {})],
            devices=jax.devices()[:8], rng=jax.random.PRNGKey(5))
        batch_g = res_g.place_batch({"input_ids": data[:, :-1],
                                     "labels": data[:, 1:]})
        loss_g, g_g = jax.jit(jax.value_and_grad(whole_batch_loss))(
            dict(res_g.state.params), batch_g)
        np.testing.assert_allclose(float(loss_1f1b), float(loss_g),
                                   atol=1e-5)
        # both grads are in the pipelined {blocks, ...} layout
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            g_1f1b, g_g)

    def test_whole_batch_loss_fn_still_rejected_with_pointer(self):
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False)
        with pytest.raises(ValueError, match="head_loss"):
            auto_accelerate(
                GPT(cfg), loss_fn=lambda p, b: 0.0,
                strategy=[("pipeline_parallel",
                           {"size": 2, "schedule": "1f1b"})],
                devices=jax.devices()[:2])

    def test_head_loss_outside_1f1b_rejected(self):
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False)
        with pytest.raises(ValueError, match="1f1b"):
            auto_accelerate(
                GPT(cfg),
                strategy=[("pipeline_parallel",
                           {"size": 2, "head_loss": lambda *a: 0.0})],
                devices=jax.devices()[:2])

    def test_head_loss_with_pp1_rejected(self):
        cfg = dataclasses.replace(GPTConfig.nano(), remat=False)
        with pytest.raises(ValueError, match="size"):
            auto_accelerate(
                GPT(cfg),
                strategy=[("pipeline_parallel",
                           {"schedule": "1f1b",
                            "head_loss": lambda *a: 0.0})],
                devices=jax.devices()[:2])
