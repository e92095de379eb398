"""Serving steps: prefill (process a full prompt, build the cache) and
decode (one new token against a seq_len-deep cache) - the objects the
``decode_*`` / ``prefill_*`` dry-run cells lower.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RunConfig
from repro.distributed import sharding as shd
from repro.models import transformer as T


def serve_prefill(params, batch, cache, *, cfg: ArchConfig, run: RunConfig):
    """Prompt pass: fills the cache, returns last-position logits.  Under
    static activation calibration an analog layer's result for a row does
    not depend on the other rows of the call, so the head runs at the
    last position alone."""
    static = run.analog.mode != "digital" and run.analog.act_calib == "static"
    logits, cache, _ = T.lm_apply(params, batch, cfg, run, cache=cache,
                                  last_only=static)
    return logits[:, -1], cache


def serve_decode(params, tokens_or_embeds, cache, *, cfg: ArchConfig,
                 run: RunConfig):
    """One decode step: [B, 1] token (or embed) -> [B, vocab] logits."""
    if cfg.embed_inputs:
        batch = {"tokens": tokens_or_embeds}
    else:
        batch = {"embeds": tokens_or_embeds}
    logits, cache, _ = T.lm_apply(params, batch, cfg, run, cache=cache)
    return logits[:, -1], cache


def cache_sharding(cfg: ArchConfig, dtype=jnp.bfloat16):
    return shd.tree_sharding(T.lm_cache_specs(cfg, dtype))


def make_serve_steps(cfg: ArchConfig, run: RunConfig, *,
                     abstract_params=None, abstract_cache=None,
                     param_specs=None):
    """Jitted (prefill, decode) with sharded params/cache, donated cache.

    Shardings resolve shape-aware; when kv_heads cannot take the model axis
    the cache shards its sequence axis instead (split-KV decode).
    ``param_specs`` overrides the raw-params logical axes - the serve
    engine passes the plan-augmented specs of its pre-lowered tree
    (``CompiledModel.sharding_specs()``) together with the matching
    ``abstract_params``."""
    pf = functools.partial(serve_prefill, cfg=cfg, run=run)
    dc = functools.partial(serve_decode, cfg=cfg, run=run)
    if shd.get_mesh() is None:
        return (jax.jit(pf, donate_argnums=(2,)),
                jax.jit(dc, donate_argnums=(2,)))
    if abstract_params is None:
        abstract_params = jax.eval_shape(
            lambda k: T.lm_init(k, cfg), jax.random.PRNGKey(0)
        )
    if param_specs is None:
        param_specs = T.lm_specs(cfg)
    pspec = shd.sharding_like(param_specs, abstract_params)
    if abstract_cache is not None:
        kv_dtype = jax.tree.leaves(abstract_cache)[0].dtype
        kv_dtype = jnp.int8 if any(
            l.dtype == jnp.int8 for l in jax.tree.leaves(abstract_cache)
        ) else jnp.bfloat16
        cspec = shd.sharding_like(T.lm_cache_specs(cfg, kv_dtype),
                                  abstract_cache)
    else:
        cspec = shd.tree_sharding(T.lm_cache_specs(cfg))
    prefill = jax.jit(
        pf,
        in_shardings=(pspec, None, cspec),
        out_shardings=(None, cspec),
        donate_argnums=(2,),
    )
    decode = jax.jit(
        dc,
        in_shardings=(pspec, None, cspec),
        out_shardings=(None, cspec),
        donate_argnums=(2,),
    )
    return prefill, decode
