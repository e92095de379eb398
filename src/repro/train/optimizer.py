"""Optimizers (pure JAX, no optax): AdamW with sharded state, cosine
schedule with linear warmup, global-norm clipping, and a trainable-mask that
freezes the analog calibration buffers (fpn, scales, gain) - those are
hardware properties, not weights (paper §III-B trains only the synaptic
weights through the HIL loop) - and the held-expert layer's routing
buffers (``expert_bias``, kept by load balancing outside the gradient,
and the ``held`` expert ids).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

FROZEN_KEYS = ("fpn", "a_scale", "w_scale", "gain", "expert_bias", "held")


def trainable_mask(params) -> dict:
    """True for leaves that receive optimizer updates."""

    def walk(tree, frozen):
        if isinstance(tree, dict):
            return {
                k: walk(v, frozen or k in FROZEN_KEYS)
                for k, v in tree.items()
            }
        return not frozen

    return walk(params, False)


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
    min_lr_frac: float = 0.1
    state_dtype: str = "float32"     # "bfloat16" halves optimizer memory


def schedule(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip(
        (step - cfg.warmup_steps)
        / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
        0.0, 1.0,
    )
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def adamw_init(params, cfg: AdamWConfig):
    dt = jnp.bfloat16 if cfg.state_dtype == "bfloat16" else jnp.float32
    mask = trainable_mask(params)
    zeros = lambda p, m: (jnp.zeros(p.shape, dt) if m
                          else jnp.zeros((), jnp.float32))
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(zeros, params, mask),
        "v": jax.tree.map(zeros, params, mask),
    }


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def adamw_update(params, grads, state, cfg: AdamWConfig):
    """Returns (new_params, new_state, metrics)."""
    mask = trainable_mask(params)
    step = state["step"] + 1
    lr = schedule(cfg, step)

    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v, trainable):
        if not trainable:
            return p, m, v
        g = g.astype(jnp.float32) * scale
        m32 = m.astype(jnp.float32)
        v32 = v.astype(jnp.float32)
        m_new = b1 * m32 + (1 - b1) * g
        v_new = b2 * v32 + (1 - b2) * g * g
        mhat = m_new / bc1
        vhat = v_new / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        p32 = p.astype(jnp.float32)
        p_new = p32 - lr * (delta + cfg.weight_decay * p32)
        return (p_new.astype(p.dtype), m_new.astype(m.dtype),
                v_new.astype(v.dtype))

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    flat_mask = treedef.flatten_up_to(mask)
    out = [upd(p, g, m, v, t) for p, g, m, v, t in
           zip(flat_p, flat_g, flat_m, flat_v, flat_mask)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    new_state = {"step": step, "m": new_m, "v": new_v}
    return new_p, new_state, {"grad_norm": gnorm, "lr": lr}


def opt_state_specs(param_specs):
    """Sharding specs for the optimizer state: mirror the parameters for
    trainable leaves, scalar (replicated) for frozen calibration buffers."""
    is_leaf = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x
    )
    mask = trainable_mask(param_specs)  # structural walk over the same keys
    mv = jax.tree.map(
        lambda s, m: s if m else (), param_specs, mask, is_leaf=is_leaf
    )
    return {"step": (), "m": mv, "v": mv}
