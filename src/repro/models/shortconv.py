"""Gated short convolution, the token mixer of LFM2's conv layers.

For ``u [B, S, d]`` (LiquidAI LFM2, ``Lfm2ShortConv``):

    [b, c, x] = split3(W_in u)          W_in: d -> 3d, analog, no bias
    v = b * x
    z_t = k_0 * v_{t-2} + k_1 * v_{t-1} + k_2 * v_t     (3 taps shown)
    y = W_out (c * z)                   W_out: d -> d, analog, no bias

The depthwise causal convolution ``k [taps, d]`` is digital fp32 (an
elementwise multiply-add, summed in tap order).  Its decode state is the
last ``taps - 1`` values of ``v`` per channel, ``[B, taps - 1, d]``: a
call continues from the state it is given (zeros before the first token)
and hands back the last ``taps - 1`` values of state-then-call, so a
prefill followed by single-token decode steps computes exactly what one
call over the whole sequence computes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.analog import AnalogConfig
from repro.core.noise import NoiseConfig
from repro.models import layers as L


def shortconv_init(key, d_model: int, taps: int = 3, *,
                   noise: NoiseConfig = NoiseConfig(), dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {
        "in_proj": L.linear_init(ks[0], d_model, 3 * d_model, noise=noise,
                                 dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (taps, d_model))
                   / jnp.sqrt(taps)).astype(jnp.float32),
        "out_proj": L.linear_init(ks[2], d_model, d_model, noise=noise,
                                  dtype=dtype),
    }


def shortconv_specs(noise: NoiseConfig = NoiseConfig()):
    return {
        "in_proj": L.linear_specs("embed", "mlp", noise=noise),
        "conv_w": (None, None),
        "out_proj": L.linear_specs("embed", "embed", noise=noise),
    }


def shortconv_module_spec(d_model: int, taps: int = 3, *,
                          noise: NoiseConfig = NoiseConfig()):
    """Declare one short-conv mixer for the api front door:
    ``api.compile(shortconv_module_spec(d), params, run)`` bakes its two
    analog projections, and ``CompiledModel.apply(u, state=)`` is
    :func:`shortconv_apply` over the pre-lowered tree."""
    from repro import api

    def _apply(model, u, *, state=None, key=None):
        return shortconv_apply(model.lower(), u, acfg=model.acfg,
                               state=state, key=key)

    return api.ModuleSpec(
        name=f"shortconv_{d_model}x{taps}",
        kind="tree",
        apply_fn=_apply,
        layers=(api.LayerSpec("in_proj", d_model, 3 * d_model),
                api.LayerSpec("out_proj", d_model, d_model)),
        param_axes=shortconv_specs(noise),
    )


def causal_conv(v, k, state):
    """``z_t = sum_j k_j * v_{t - (taps-1) + j}`` over ``state`` then
    ``v [B, S, d]``; returns ``(z, new state)``."""
    taps = k.shape[0]
    ext = jnp.concatenate([state.astype(jnp.float32), v], axis=1)
    s = v.shape[1]
    z = k[0] * ext[:, 0:s]
    for j in range(1, taps):
        z = z + k[j] * ext[:, j:j + s]
    return z, ext[:, s:]


def shortconv_apply(params, u, *, acfg: AnalogConfig, state=None,
                    key=None):
    """``u [B, S, d] -> (y [B, S, d], new state [B, taps - 1, d])``;
    ``state`` None starts from zeros (a whole sequence, no cache)."""
    b, s, d = u.shape
    k = params["conv_w"].astype(jnp.float32)
    ks = jax.random.split(key, 2) if key is not None else (None, None)
    with jax.named_scope("lfm2.shortconv"):
        bcx = L.linear_apply(params["in_proj"], u, acfg, key=ks[0])
        bg, cg, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
        if state is None:
            state = jnp.zeros((b, k.shape[0] - 1, d), jnp.float32)
        z, new_state = causal_conv(bg * x, k, state)
        y = L.linear_apply(params["out_proj"], (cg * z).astype(u.dtype),
                           acfg, key=ks[1])
    return y, new_state
