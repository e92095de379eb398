"""Pallas kernel for the FPGA preprocessing hot loop (paper Fig. 7):
non-overlapping max-min window pooling over the derivative signal.

On the real system this runs in FPGA fabric at line rate; on TPU it is a
bandwidth-bound streaming reduce, so the kernel tiles the time axis into
VMEM-resident blocks and emits one output element per 32-sample window.
The windows arrive as the trailing axis of a ``[B, T/32, 32]`` view made
outside the kernel: Mosaic lowers a reduction over that axis, but not an
in-kernel reshape of a flat ``[B, T]`` block into windows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, o_ref):
    xw = x_ref[...]                      # [bb, bt, window]
    o_ref[...] = xw.max(axis=-1) - xw.min(axis=-1)


@functools.partial(jax.jit, static_argnames=("window", "block_b", "block_t",
                                             "interpret"))
def maxmin_pool_pallas(
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
