"""AdamW + cosine schedule + global-norm clipping, pure JAX.

Optimizer moments are kept in f32 regardless of the parameter dtype.
Their ZeRO-1 layout (split over the data axes as well as the parameter's
own) is ``repro.plans.adamw_shardings``; XLA inserts the collectives the
update then needs.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def cosine_schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(1.0, cfg.warmup_steps)
    prog = (step - cfg.warmup_steps) / jnp.maximum(
        1.0, cfg.total_steps - cfg.warmup_steps)
    prog = jnp.clip(prog, 0.0, 1.0)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def adamw_init(params) -> dict:
    f32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(f32, params),
        "v": jax.tree.map(f32, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


@jax.named_scope("adamw")
def adamw_update(params, grads, state, cfg: AdamWConfig):
    """Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    lr = cosine_schedule(cfg, step)

    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))

    b1, b2 = cfg.b1, cfg.b2
    t = step.astype(jnp.float32)
    bc1 = 1.0 - b1 ** t
    bc2 = 1.0 - b2 ** t

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / bc1
        vhat = v / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        new_p = p.astype(jnp.float32) - lr * delta
        return new_p.astype(p.dtype), m, v

    out = jax.tree.map(upd, params, grads, state["m"], state["v"])
    new_params = jax.tree.map(lambda o: o[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda o: o[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda o: o[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, {"m": new_m, "v": new_v, "step": step}, metrics
