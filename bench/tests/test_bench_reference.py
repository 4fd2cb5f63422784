"""The plain reference agrees with the program on the CPU at toy size:
its logits with ``lm.forward``, its loss, first gradient and update with
the program's train step."""

import jax
import jax.numpy as jnp
import numpy as np

from bench.core import weights
from bench.core.spec import program_arch
from bench.reference import dense

OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "clip_norm": 1.0, "warmup_steps": 0, "total_steps": 1000,
       "min_lr_ratio": 0.1}


def test_logits_match_program(toy):
    from repro.models import lm

    arch = program_arch(toy)
    params = weights.make_params(toy, seed=3)
    weights.check_layout(params, arch)
    toks = jax.random.randint(jax.random.key(0), (24,), 0, toy["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = lm.forward(params, {"tokens": toks[None]}, arch)[0][0]
    got = jax.jit(lambda p, t: dense.logits(p, t, toy))(params, toks)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_train_readings_match_program(toy):
    from repro.optim import AdamWConfig, adamw_init
    from repro.train import TrainConfig, make_train_step

    arch = program_arch(toy)
    seed, B, S = 5, 4, 32
    batches = [weights.make_batch(seed, i, B, S, toy["vocab_size"])
               for i in range(3)]
    step = jax.jit(make_train_step(arch, None, TrainConfig(
        optimizer=AdamWConfig(**OPT), q_chunk=16, loss_chunk=16)))
    params = weights.make_params(toy, seed)
    opt = adamw_init(params)
    losses = []
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            params, opt, met = step(params, opt, b)
            losses.append(float(met["loss"]))
            if i == 0:
                grad = weights.leaf_norms(opt["m"], 1 / (1 - OPT["b1"]))
        change = weights.leaf_change_norms(toy, seed, params)
        ref = dense.train_readings(
            weights.make_params(toy, seed), [b["tokens"] for b in batches],
            toy, OPT, toy["z_loss_coef"],
            lambda p: weights.leaf_change_norms(toy, seed, p),
            devices=jax.devices()[:1], rows_per_block=2)
    np.testing.assert_allclose(losses, ref["loss"], rtol=1e-5)
    for k, v in ref["grad_norm"].items():
        np.testing.assert_allclose(grad[k], v, rtol=1e-3, err_msg=k)
    for k, v in ref["change_norm"].items():
        np.testing.assert_allclose(change[k], v, rtol=1e-3, err_msg=k)
