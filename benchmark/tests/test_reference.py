"""The plain reference against the program's GPT at a tiny size on the
CPU, both in float32: same parameters, same loss, same gradient norm."""

import jax
import jax.numpy as jnp

from benchmark import cells
from benchmark.data import make_data
from benchmark.reference import loss_and_grad_norm


def test_reference_matches_program_at_nano_f32():
    cfg = dict(cells.load_cell("gpt2_124m.steady")["config"])
    cfg.update(vocab_size=512, n_layer=2, n_head=2, n_embd=128,
               n_positions=64)
    cfg["program"] = dict(cfg["program"], dtype="float32",
                          use_flash_attention=False)
    mod = cells.load_module("models", "gpt")
    model = mod.build(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    batch = {k: jnp.asarray(v) for k, v in
             make_data(512, 4, 64, seed=3)(0).items()}
    from dlrover_wuqiong_tpu.trainer.train_step import make_lm_loss

    sys_loss, sys_norm = loss_and_grad_norm(make_lm_loss(model.apply),
                                            params, batch)
    ref_loss, ref_norm = loss_and_grad_norm(mod.reference_loss(cfg),
                                            params, batch,
                                            precision="highest")
    # float32 on both sides: only the order of sums differs
    assert abs(sys_loss - ref_loss) / ref_loss < 1e-5
    assert abs(sys_norm - ref_norm) / ref_norm < 1e-4


def test_data_is_a_pure_function_of_seed_and_step():
    a, b = make_data(512, 4, 64, seed=7), make_data(512, 4, 64, seed=7)
    assert (a(5)["input_ids"] == b(5)["input_ids"]).all()
    assert (a(5)["labels"][:, :-1] == a(5)["input_ids"][:, 1:]).all()
    assert (a(5)["input_ids"] != a(6)["input_ids"]).any()
    c = make_data(512, 4, 64, seed=8)
    assert (a(5)["input_ids"] != c(5)["input_ids"]).any()
