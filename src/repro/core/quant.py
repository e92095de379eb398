"""Quantizers for the BSS-2 datapath (paper Fig. 4) with straight-through
estimators for hardware-in-the-loop training (paper §III-B).

- activations: 5-bit unsigned pulse lengths, values in [0, 31]
- weights:     6-bit signed synaptic weights, values in [-63, 63]
- ADC:         8-bit signed readout, values in [-128, 127]

The STE follows the classic QAT recipe: forward uses the quantized value,
backward passes the gradient through unchanged *inside* the clip range and
zero outside it (so the float master weights keep learning).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.hw import BSS2

# Precision of every analog partial-sum matmul (a chunk's codes times its
# effective weights).  The sums are defined in fp32 - the oracle, and what
# the CPU computes - but a TPU contracts fp32 operands at bf16 by default,
# which rounds the gain-folded effective weights and flips ADC codes.
ANALOG_PRECISION = jax.lax.Precision.HIGHEST


def _round_ste(x: jax.Array) -> jax.Array:
    """Round with a straight-through gradient."""
    return x + jax.lax.stop_gradient(jnp.round(x) - x)


def _clip_ste(x: jax.Array, lo: float, hi: float) -> jax.Array:
    """Clip whose gradient is masked outside [lo, hi] (saturation kills grad)."""
    return jnp.clip(x, lo, hi)  # jnp.clip already has the masked gradient


def quantize_act(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Quantize activations to 5-bit unsigned codes (float dtype, integer values).

    ``scale`` is the LSB size: code = clip(round(x / scale), 0, 31).
    Negative inputs saturate at 0 (the synapse drivers only emit pulses for
    positive activations) - callers that need signed inputs use the split or
    offset encodings in :mod:`repro.core.analog`.
    """
    return _clip_ste(_round_ste(x / scale), 0.0, float(BSS2.a_max))


def dequantize_act(code: jax.Array, scale: jax.Array) -> jax.Array:
    return code * scale


def quantize_weight(w: jax.Array, scale: jax.Array) -> jax.Array:
    """Quantize weights to 6-bit signed codes (float dtype, integer values).

    ``scale`` broadcasts; per-output-column scales are the default in
    :class:`repro.core.analog.AnalogLinear` (each neuron column is calibrated
    independently on BSS-2, cf. Weis et al. 2020).
    """
    return _clip_ste(_round_ste(w / scale), -float(BSS2.w_max), float(BSS2.w_max))


def dequantize_weight(code: jax.Array, scale: jax.Array) -> jax.Array:
    return code * scale


def act_scale_from_max(max_abs: jax.Array) -> jax.Array:
    """LSB so that ``max_abs`` maps to the top activation code."""
    return jnp.maximum(max_abs, 1e-8) / float(BSS2.a_max)


def weight_scale_from_max(max_abs: jax.Array) -> jax.Array:
    """LSB so that ``max_abs`` maps to the top weight code."""
    return jnp.maximum(max_abs, 1e-8) / float(BSS2.w_max)


def calibrate_act_scale(x: jax.Array, pct: float = 99.9) -> jax.Array:
    """Percentile-calibrated activation scale (robust against outliers)."""
    hi = jnp.percentile(jax.lax.stop_gradient(jnp.abs(x)), pct)
    return act_scale_from_max(hi)


def calibrate_weight_scale(w: jax.Array, per_column: bool = True) -> jax.Array:
    """Per-column (neuron) weight scale, matching per-neuron calibration."""
    wa = jax.lax.stop_gradient(jnp.abs(w))
    if per_column:
        return weight_scale_from_max(wa.max(axis=0, keepdims=True))
    return weight_scale_from_max(wa.max())


def adc_readout(v: jax.Array) -> jax.Array:
    """8-bit saturating ADC conversion (round + clip), STE gradient."""
    return _clip_ste(_round_ste(v), float(BSS2.adc_min), float(BSS2.adc_max))


def requantize_5bit(adc_code: jax.Array, shift: int) -> jax.Array:
    """SIMD-CPU requantization of ADC results to 5-bit input activations.

    The paper (II-A): "converted to 5 bit input activations by subtracting
    V_reset and applying bitwise right-shifts".  ``adc_code`` is already
    V_reset-relative; a right shift by ``shift`` bits maps it onto [0, 31].
    Uses floor-division semantics like the hardware shift; STE gradient.
    """
    shifted = adc_code / float(1 << shift)
    floored = shifted + jax.lax.stop_gradient(jnp.floor(shifted) - shifted)
    return _clip_ste(floored, 0.0, float(BSS2.a_max))
