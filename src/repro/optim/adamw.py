"""AdamW with decoupled weight decay, cosine schedule, global-norm clipping.

Pure-pytree implementation (no optax in this environment).  Moments are
fp32 regardless of the parameter dtype (bf16 params keep fp32 "master"
precision inside the update: p32 = p + update computed in fp32, cast back).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def lr_at(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    """Linear warmup → cosine decay to min_lr_ratio·lr."""
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(cfg.warmup_steps, 1)
    t = (step - cfg.warmup_steps) / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1)
    t = jnp.clip(t, 0.0, 1.0)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def init_opt_state(params: PyTree) -> dict:
    zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(zeros32, params),
        "v": jax.tree.map(zeros32, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree: PyTree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def adamw_update(params: PyTree, grads: PyTree, state: dict,
                 cfg: AdamWConfig) -> tuple[PyTree, dict]:
    """One AdamW step, clipping included, under the device-side scope
    ``optimizer``."""
    with jax.named_scope("optimizer"):
        step = state["step"] + 1
        gn = global_norm(grads)
        clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gn, 1e-9))
        lr = lr_at(cfg, step)
        b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
        b2c = 1 - cfg.b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32) * clip
            m = cfg.b1 * m + (1 - cfg.b1) * g
            v = cfg.b2 * v + (1 - cfg.b2) * g * g
            mhat = m / b1c
            vhat = v / b2c
            p32 = p.astype(jnp.float32)
            p32 = p32 - lr * (mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p32)
            return p32.astype(p.dtype), m, v

        flat_p, treedef = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        flat_m = jax.tree.leaves(state["m"])
        flat_v = jax.tree.leaves(state["v"])
        new_p, new_m, new_v = [], [], []
        for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
            a, b, c = upd(p, g, m, v)
            new_p.append(a)
            new_m.append(b)
            new_v.append(c)
        return (jax.tree.unflatten(treedef, new_p),
                {"m": jax.tree.unflatten(treedef, new_m),
                 "v": jax.tree.unflatten(treedef, new_v),
                 "step": step})
