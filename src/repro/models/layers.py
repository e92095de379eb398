"""Shared model building blocks: norms, RoPE (incl. M-RoPE), embeddings,
MLPs - every parameter matmul runs through the analog backend.

Module convention (pure JAX, no flax): each block provides
``<name>_init(key, ...) -> params``, ``<name>_apply(params, x, ...) -> y``
and ``<name>_specs(...) -> pytree of logical-axis tuples`` mirroring params.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.api.program import apply_linear
from repro.core.analog import AnalogConfig, analog_linear_init
from repro.core.hw import BSS2
from repro.core.noise import NoiseConfig
from repro.distributed.sharding import constrain


# ---------------------------------------------------------------- linear
def linear_init(key, in_dim, out_dim, *, bias=False,
                noise: NoiseConfig = NoiseConfig(), w_init_scale=1.0,
                dtype=jnp.float32):
    return analog_linear_init(
        key, in_dim, out_dim, bias=bias, noise=noise,
        w_init_scale=w_init_scale, dtype=dtype,
    )


def linear_apply(params, x, acfg: AnalogConfig, *, key=None):
    return apply_linear(params, x, acfg, key=key)


def linear_lower(params, acfg: AnalogConfig, **kw):
    """DEPRECATED: use ``repro.api.compile(api.linear_spec(...), ...)``.
    Kept as a bit-exact shim over the api front door (ISSUE 2)."""
    import warnings

    warnings.warn(
        "linear_lower is deprecated; use repro.api.compile with "
        "api.linear_spec (CompiledModel.lower() returns the AnalogPlan)",
        DeprecationWarning, stacklevel=2,
    )
    from repro import api

    if set(kw) - {"signed_input"}:
        # exotic per-layer options (epilogue/shift/...) go straight to the
        # exec substrate the api drives - same lowering, no spec wrapper
        from repro.exec.lower import lower as lower_plan

        return lower_plan(params, acfg, **kw)
    k, n = params["w"].shape
    spec = api.linear_spec(k, n, signed_input=kw.get("signed_input"))
    return api.compile(spec, params, acfg).lower()


def linear_specs(in_name: Optional[str], out_name: Optional[str],
                 *, bias=False, noise: NoiseConfig = NoiseConfig()):
    specs = {
        "w": (in_name, out_name),
        "w_scale": (None, out_name),
        "a_scale": (),
        "gain": (),
    }
    if bias:
        specs["b"] = (out_name,)
    if noise.mode != "none":
        fpn = {}
        if noise.gain_std > 0:
            if noise.mode == "full":
                fpn["gain"] = (in_name, out_name)
            else:
                fpn["row_gain"] = (in_name,)
                fpn["col_gain"] = (out_name,)
        if noise.offset_std > 0:
            fpn["chunk_offset"] = ("chunks", out_name)
        if fpn:
            specs["fpn"] = fpn
    return specs


# ------------------------------------------------------- ordered sums
def ordered_sum(x: jax.Array) -> jax.Array:
    """Sum over the last axis in one fixed pairwise order (halves added
    elementwise, an odd last element carried), keeping the axis.

    This is the order of every digital glue sum of the models: the norms'
    means, the softmax denominators (dense, decode and each flash block)
    and the routing weights' totals.  A ``reduce`` leaves its order to
    the compiler, which picks it per fusion: two programs of the same
    fp32 math then part in the last bit, and a 5-bit input quantizer
    downstream turns such a bit into a code.  These adds are elementwise,
    so the order is part of the arithmetic: every program that keeps it
    gets the same bits, and a rewrite that sums another way computes a
    different result."""
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        head = x[..., :h] + x[..., h:2 * h]
        x = head if x.shape[-1] == 2 * h else jnp.concatenate(
            [head, x[..., 2 * h:]], axis=-1)
    return x


def softmax(x: jax.Array) -> jax.Array:
    """Softmax over the last axis, its denominator summed in
    :func:`ordered_sum`'s order."""
    e = jnp.exp(x - jax.lax.stop_gradient(x.max(-1, keepdims=True)))
    return e / ordered_sum(e)


# ----------------------------------------------------------------- norms
def norm_init(dim, kind="rmsnorm"):
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


def norm_apply(params, x, kind="rmsnorm", eps=1e-5):
    """RMSNorm or LayerNorm over the last axis, every mean summed in
    :func:`ordered_sum`'s order."""
    xf = x.astype(jnp.float32)
    n = xf.shape[-1]
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(ordered_sum(xf * xf) / n + eps)
    elif kind == "layernorm":
        xc = xf - ordered_sum(xf) / n
        y = xc * jax.lax.rsqrt(ordered_sum(xc * xc) / n + eps)
    else:
        raise ValueError(kind)
    y = y * params["scale"]
    if "bias" in params:
        y = y + params["bias"]
    return y.astype(x.dtype)


def norm_specs(kind="rmsnorm"):
    p = {"scale": (None,)}
    if kind == "layernorm":
        p["bias"] = (None,)
    return p


# ------------------------------------------------------------------ RoPE
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    # lax.iota (a traced op) instead of jnp.arange (a concrete constant):
    # the frequency table is also built INSIDE the fused-block pallas
    # kernel, whose trace may not capture constants.  XLA constant-folds
    # it right back everywhere else.
    even = 2.0 * jax.lax.iota(jnp.float32, head_dim // 2)
    return 1.0 / (theta ** (even / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, dh]; positions: [B, S] int32."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                       # [dh/2]
    angle = positions[..., None].astype(jnp.float32) * freqs  # [B, S, dh/2]
    cos = jnp.cos(angle)[:, :, None, :]
    sin = jnp.sin(angle)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections=(16, 24, 24)) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL, arXiv:2409.12191): head_dim/2 frequency
    slots split into (temporal, height, width) sections, each rotated by its
    own position id.  positions: [B, S, 3] int32."""
    dh = x.shape[-1]
    assert sum(sections) == dh // 2, (sections, dh)
    freqs = rope_freqs(dh, theta)                       # [dh/2]
    pos = positions.astype(jnp.float32)                 # [B, S, 3]
    sec_ids = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=dh // 2
    )                                                    # [dh/2] in {0,1,2}
    pos_per_freq = jnp.take_along_axis(
        pos[..., None, :], sec_ids[None, None, :, None], axis=-1
    )[..., 0]                                            # [B, S, dh/2]
    angle = pos_per_freq * freqs
    cos = jnp.cos(angle)[:, :, None, :]
    sin = jnp.sin(angle)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------- embedding
def embedding_init(key, vocab, dim, dtype=jnp.float32):
    return {"table": (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)}


def embedding_apply(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def embedding_specs():
    return {"table": ("vocab", "embed")}


# ------------------------------------------------------------------- MLP
def mlp_init(key, d_model, d_ff, *, act="swiglu",
             noise: NoiseConfig = NoiseConfig(), dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {
        "up": linear_init(ks[0], d_model, d_ff, noise=noise, dtype=dtype),
        "down": linear_init(ks[1], d_ff, d_model, noise=noise, dtype=dtype),
    }
    if act == "swiglu":
        p["gate"] = linear_init(ks[2], d_model, d_ff, noise=noise, dtype=dtype)
    return p


def mlp_apply(params, x, acfg: AnalogConfig, *, act="swiglu", key=None):
    ks = jax.random.split(key, 3) if key is not None else (None,) * 3
    up = linear_apply(params["up"], x, acfg, key=ks[0])
    if act == "swiglu":
        gate = linear_apply(params["gate"], x, acfg, key=ks[1])
        h = jax.nn.silu(gate) * up
    elif act == "gelu":
        h = jax.nn.gelu(up)
    elif act == "relu":
        h = jax.nn.relu(up)
    elif act == "relu2":      # squared ReLU (Nemotron/Minitron, Primer)
        h = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(act)
    h = constrain(h, "batch", "seq", "mlp")
    return linear_apply(params["down"], h, acfg, key=ks[2])


def mlp_specs(*, act="swiglu", noise: NoiseConfig = NoiseConfig()):
    p = {
        "up": linear_specs("embed", "mlp", noise=noise),
        "down": linear_specs("mlp", "embed", noise=noise),
    }
    if act == "swiglu":
        p["gate"] = linear_specs("embed", "mlp", noise=noise)
    return p
