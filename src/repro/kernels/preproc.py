"""Pallas kernels of the FPGA pre-processing chain (paper Fig. 7).

On the real system the chain runs in FPGA fabric at line rate; on TPU it
is a bandwidth-bound streaming pass, so :func:`preprocess_pallas` does the
whole chain in one kernel that reads each raw sample once:

- It reads the raw f32[B, C, T] samples in the layout they arrive in
  (``{2,1,0:T(2,128)}`` for two channels on v5e): each block spans 128
  whole recordings, ``[128, C, T]``, whose trailing ``(C, T)`` dims are
  the array's own, so nothing is padded, copied or transposed before the
  kernel.
- In VMEM, channel by channel, it forms the derivative
  ``raw[t+1] - raw[t]`` of the first ``(T-1)//window * window`` samples
  as two loads one lane apart.
- It transposes the derivative so that time lies on sublanes and the
  block's recordings on lanes.  A window is then ``window`` consecutive
  sublanes, whole (8, 128) tiles for a window of 32, and its max - min
  is a reduction over them.  (Reducing 32-lane windows along lanes
  instead, by lane rotations and a pick of every 32nd lane, measured
  nine times slower on v5e.)
- It applies ``floor(v / 2**quant_shift)``, clips to ``[0, a_max]``,
  transposes the codes back and writes f32[B, C, (T-1)//window].

``window`` and ``quant_shift`` are compile-time constants of the kernel,
so a call carries only the samples.  The custom call is named
``maxmin_pool_pallas``: the chip benchmark's trace reader finds the stage
under that name.

:func:`maxmin_pool_2d_pallas` is the generic pooling kernel over the
rows of a ``[B, T]`` array (``kernels.ops.maxmin_pool``); its windows
arrive as the trailing axis of a ``[B, T/32, 32]`` view made outside the
kernel, since Mosaic does not reshape lanes into windows inside one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import BSS2


def _preprocess_kernel(x_ref, o_ref, *, window, quant_shift):
    t_out = o_ref.shape[-1]
    t = t_out * window
    for c in range(x_ref.shape[1]):
        d = x_ref[:, c, pl.ds(1, t)] - x_ref[:, c, pl.ds(0, t)]   # [bb, t]
        # time on sublanes: a window is `window` consecutive sublanes
        w = d.T.reshape(t_out, window, d.shape[0])
        pooled = w.max(axis=1) - w.min(axis=1)                 # [t_out, bb]
        # floor(v / 2**s) as a multiply: the same correctly rounded value
        codes = jnp.floor(pooled * (1.0 / (1 << quant_shift)))
        o_ref[:, c, :] = jnp.clip(codes, 0.0, float(BSS2.a_max)).T


@functools.partial(jax.jit, static_argnames=("window", "quant_shift",
                                             "interpret"))
def preprocess_pallas(
    raw: jax.Array,           # f32[B, C, T] raw samples
    *,
    window: int = 32,
    quant_shift: int = 4,
    interpret: bool = False,
) -> jax.Array:
    """Derivative, max-min pooling over ``window`` and 5-bit quantization
    of raw samples in one kernel: f32[B, C, T] -> f32[B, C, (T-1)//window]
    integer-valued codes in ``[0, BSS2.a_max]``."""
    b, c, t = raw.shape
    t_out = (t - 1) // window
    bb = min(128, b)                  # recordings per block: one lane each
    return pl.pallas_call(
        functools.partial(_preprocess_kernel, window=window,
                          quant_shift=quant_shift),
        grid=(pl.cdiv(b, bb),),
        in_specs=[pl.BlockSpec((bb, c, t), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((bb, c, t_out), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c, t_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
        name="maxmin_pool_pallas",
    )(raw.astype(jnp.float32))


def _kernel(x_ref, o_ref):
    xw = x_ref[...]                      # [bb, bt, window]
    o_ref[...] = xw.max(axis=-1) - xw.min(axis=-1)


@functools.partial(jax.jit, static_argnames=("window", "block_b", "block_t",
                                             "interpret"))
def maxmin_pool_2d_pallas(
    x: jax.Array,             # [B, T]
    *,
    window: int = 32,
    block_b: int = 8,
    block_t: int = 128,       # output elements per block (x block: 128*32)
    interpret: bool = False,
) -> jax.Array:
    b, t = x.shape
    assert t % window == 0, (t, window)
    t_out = t // window
    pb = (-b) % block_b
    pt = (-t_out) % block_t
    if pb or pt:
        x = jnp.pad(x, ((0, pb), (0, pt * window)))
    bb, tt_out = b + pb, t_out + pt
    out = pl.pallas_call(
        _kernel,
        grid=(bb // block_b, tt_out // block_t),
        in_specs=[pl.BlockSpec((block_b, block_t, window),
                               lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((block_b, block_t), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bb, tt_out), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(x.reshape(bb, tt_out, window))
    return out[:b, :t_out]
