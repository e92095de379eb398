"""The FPGA preprocessing chain of paper Fig. 7, bit-exact:

  raw 12-bit samples
    -> discrete derivative          (suppresses baseline fluctuations)
    -> max-min pooling over 32      (rate reduction, positive activations)
    -> 5-bit quantization           (input activations for the analog VMM)

On hardware this runs in FPGA fabric at line rate; here it is a jitted JAX
function whose pooling hot loop can dispatch to the Pallas kernel, called
through a host stage that times each dispatch while ``repro.obs`` or a
profiler session is recording.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.hw import BSS2
from repro.kernels import ops as kernel_ops

POOL_WINDOW = 32


def preprocess(raw: jax.Array, *, window: int = POOL_WINDOW,
               quant_shift: int = 4, use_pallas: bool = False) -> jax.Array:
    """raw: [..., C, T] 12-bit sample values -> [..., C, (T-1)//window]
    5-bit activation codes (integer-valued float32).

    ``quant_shift``: right-shift applied by the FPGA quantizer; 4 bits maps
    the typical max-min derivative range (<512 counts) onto [0, 31].

    The host stage around the jitted chain: while a collector or a
    profiler session is recording (``obs.observed()``), an eager call
    dispatches it inside the span ``ecg.preprocess`` and records the
    span's host time in the histogram ``ecg.preprocess_us``.  Otherwise,
    and inside an outer trace, it is the chain alone.
    """
    if not obs.observed() or isinstance(raw, jax.core.Tracer):
        return _preprocess(raw, window=window, quant_shift=quant_shift,
                           use_pallas=use_pallas)
    with obs.span("ecg.preprocess") as sp:
        codes = _preprocess(raw, window=window, quant_shift=quant_shift,
                            use_pallas=use_pallas)
    obs.histogram("ecg.preprocess_us").record(sp.dur_us)
    return codes


@functools.partial(jax.jit, static_argnames=("window", "use_pallas"))
def _preprocess(raw: jax.Array, *, window: int = POOL_WINDOW,
                quant_shift: int = 4, use_pallas: bool = False) -> jax.Array:
    """The jitted chain of :func:`preprocess`, one named scope a stage."""
    with jax.named_scope("ecg.derivative"):
        deriv = jnp.diff(raw, axis=-1)                   # discrete derivative
        t = deriv.shape[-1]
        t_trunc = (t // window) * window
        deriv = deriv[..., :t_trunc]
    with jax.named_scope("ecg.maxmin_pool"):
        pooled = kernel_ops.maxmin_pool(deriv, window, use_pallas=use_pallas)
    with jax.named_scope("ecg.quantize"):
        codes = jnp.floor(pooled / (1 << quant_shift))
        return jnp.clip(codes, 0, BSS2.a_max).astype(jnp.float32)


def preprocess_batch(raw_batch, **kw):
    """[N, C, T] raw records -> [N, C, T'] activation codes."""
    return preprocess(jnp.asarray(raw_batch), **kw)
