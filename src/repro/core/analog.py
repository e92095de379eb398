"""The analog-inference execution backend: BSS-2 VMM semantics as a
composable JAX operator plus the ``AnalogLinear`` module built on it.

Faithful dataflow (paper Fig. 4 + §II-A + hxtorch row-split semantics):

    a_code  = clip(round(x / a_scale), 0, 31)                  # 5-bit events
    w_code  = clip(round(w / w_scale), -63, 63)                # 6-bit synapses
    w_eff   = w_code * (1 + fixed_pattern_gain)                # analog mismatch
    per 128-row chunk c:
        v_c   = gain * (a_chunk @ w_eff_chunk) + offset_c + readout_c
        adc_c = clip(round(v_c), -128, 127)                    # saturating ADC
    y_int   = sum_c adc_c                                      # digital sum
    y       = y_int * a_scale * w_scale / gain  (+ bias)       # dequantize

Two execution modes:
- ``analog_faithful``: exactly the above (per-chunk ADC saturation before the
  digital partial-sum accumulation) - the paper-faithful baseline.
- ``analog_fast``: beyond-paper variant that accumulates all chunks in fp32
  and applies a single saturating conversion at the end (range scaled by the
  number of chunks).  One large matmul instead of C small ones -> much better
  MXU utilization; sacrifices bit-exact intermediate saturation.

Training (paper §III-B, hardware-in-the-loop): every round/clip carries a
straight-through gradient, so ``jax.grad`` through this module reproduces the
HIL scheme - forward through the (noisy, saturating) hardware model, backward
through the quantized linearization onto the float master weights.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import noise as noise_lib
from repro.core import quant
from repro.core.hw import BSS2
from repro.core.noise import NoiseConfig

Params = dict


@dataclasses.dataclass(frozen=True)
class AnalogConfig:
    """Execution configuration for analog layers (how to run, not what)."""

    mode: str = "analog_faithful"   # "digital" | "analog_faithful" | "analog_fast"
    signed_input: str = "split"     # "none" | "split" | "offset"
    act_calib: str = "dynamic"      # "dynamic" (per-call abs-max) | "static"
    chunk_rows: int = BSS2.signed_rows
    gain_headroom: float = 3.0      # sigma headroom against chunk saturation
    act_rms_codes: float = 9.0      # assumed RMS of activation codes (calib.)
    noise: NoiseConfig = dataclasses.field(default_factory=NoiseConfig)
    deterministic: bool = True      # no temporal readout noise (standalone mode)
    # the analog VMM's implementation: None = Pallas on TPU, the jnp
    # oracle elsewhere; True/False force one (repro.kernels.ops)
    use_pallas: Optional[bool] = None

    def replace(self, **kw) -> "AnalogConfig":
        return dataclasses.replace(self, **kw)


DIGITAL = AnalogConfig(mode="digital", noise=noise_lib.NOISELESS)


# --------------------------------------------------------------------------
# core emulation op: the deterministic VMM is repro.kernels.ops.analog_mvm
# (Pallas kernel or jnp oracle); only readout noise is emulated here
# --------------------------------------------------------------------------
def _pad_to_chunks(a_code: jax.Array, w_eff: jax.Array, chunk_rows: int):
    k = a_code.shape[-1]
    pad = (-k) % chunk_rows
    if pad:
        a_code = jnp.pad(a_code, [(0, 0)] * (a_code.ndim - 1) + [(0, pad)])
        w_eff = jnp.pad(w_eff, [(0, pad), (0, 0)])
    return a_code, w_eff, (k + pad) // chunk_rows


def analog_matmul(
    a_code: jax.Array,
    w_eff: jax.Array,
    gain: jax.Array,
    chunk_offset: Optional[jax.Array],
    readout_key: Optional[jax.Array],
    cfg: AnalogConfig,
) -> jax.Array:
    """Chunked saturating analog VMM.  Returns integer-valued float [..., N]
    (the digitally accumulated ADC codes).

    a_code: [..., K] integer-valued float in [0, 31]
    w_eff:  [K, N] effective analog weights (quantized codes x fp gain)
    gain:   scalar or [N] analog gain (code domain)
    chunk_offset: [C, N] fixed-pattern ADC offsets or None

    Without temporal readout noise (``cfg.deterministic``, no key, or no
    readout spread) this is :func:`repro.kernels.ops.analog_mvm` under
    its HIL gradient.  With noise the chunk loop is emulated here: no
    kernel draws readout noise.
    """
    a_code, w_eff, n_chunks = _pad_to_chunks(a_code, w_eff, cfg.chunk_rows)
    n = w_eff.shape[-1]
    batch_shape = a_code.shape[:-1]
    fast = cfg.mode == "analog_fast"
    rn = None
    if not cfg.deterministic:
        rn = noise_lib.readout_noise(
            readout_key,
            batch_shape + ((n,) if fast else (n_chunks, n)), cfg.noise,
        )

    if rn is None:
        from repro.kernels import ops as kernel_ops

        y2 = kernel_ops.analog_mvm(
            a_code.reshape(-1, a_code.shape[-1]), w_eff,
            jnp.broadcast_to(jnp.asarray(gain, jnp.float32), (n,)),
            chunk_offset, cfg.chunk_rows, not fast, cfg.use_pallas,
        )
        return y2.reshape(batch_shape + (n,))

    if fast:
        # beyond-paper: one fused matmul, single final saturation with the
        # accumulated range (C * [-128, 127]).
        total = jnp.einsum(
            "...k,kn->...n", a_code, w_eff,
            precision=quant.ANALOG_PRECISION,
            preferred_element_type=jnp.float32,
        )
        v = total * gain
        if chunk_offset is not None:
            v = v + chunk_offset.sum(axis=0)
        v = v + rn * jnp.sqrt(float(n_chunks))
        lo = float(BSS2.adc_min) * n_chunks
        hi = float(BSS2.adc_max) * n_chunks
        return jnp.clip(quant._round_ste(v), lo, hi)

    # faithful: per-chunk ADC before digital accumulation
    a_c = a_code.reshape(batch_shape + (n_chunks, cfg.chunk_rows))
    w_c = w_eff.reshape(n_chunks, cfg.chunk_rows, n)
    v = jnp.einsum(
        "...ck,ckn->...cn", a_c, w_c, precision=quant.ANALOG_PRECISION,
        preferred_element_type=jnp.float32,
    )
    v = v * gain
    if chunk_offset is not None:
        v = v + chunk_offset
    v = v + rn
    adc = quant.adc_readout(v)
    return adc.sum(axis=-2)


# --------------------------------------------------------------------------
# AnalogLinear module
# --------------------------------------------------------------------------
def analog_linear_init(
    key: jax.Array,
    in_dim: int,
    out_dim: int,
    *,
    bias: bool = False,
    noise: NoiseConfig = NoiseConfig(),
    chunk_rows: int = BSS2.signed_rows,
    w_init_scale: float = 1.0,
    dtype=jnp.float32,
) -> Params:
    """Initialize master weights, static quantization scales, the analog gain
    and the frozen fixed-pattern noise for one logical linear layer."""
    k_w, k_n = jax.random.split(key)
    std = w_init_scale / jnp.sqrt(in_dim)
    w = (std * jax.random.normal(k_w, (in_dim, out_dim))).astype(dtype)
    n_chunks = -(-in_dim // chunk_rows)
    params = {
        "w": w,
        "w_scale": quant.calibrate_weight_scale(w.astype(jnp.float32)),
        # activation scale: static, recalibratable via calibrate()
        "a_scale": jnp.asarray(1.0 / BSS2.a_max, jnp.float32),
        "gain": _statistical_gain(w.astype(jnp.float32), chunk_rows),
    }
    if bias:
        params["b"] = jnp.zeros((out_dim,), dtype)
    fpn = noise_lib.init_fixed_pattern(k_n, in_dim, out_dim, n_chunks, noise)
    if fpn:
        params["fpn"] = fpn
    return params


def _statistical_gain(w: jax.Array, chunk_rows: int,
                      act_rms: float = 9.0, headroom: float = 3.0) -> jax.Array:
    """Analog gain so that ``headroom`` sigmas of the typical chunk partial sum
    stay inside the 8-bit ADC range (per-layer calibration, Weis et al.)."""
    w_scale = quant.calibrate_weight_scale(w)
    w_code_rms = jnp.sqrt(jnp.mean((w / w_scale) ** 2) + 1e-6)
    partial_rms = jnp.sqrt(float(chunk_rows)) * act_rms * w_code_rms
    return jnp.minimum(1.0, float(BSS2.adc_max) / (headroom * partial_rms + 1e-6))


def calibrate(params: Params, x_sample: jax.Array, pct: float = 99.9) -> Params:
    """Recalibrate the static activation scale from sample data."""
    out = dict(params)
    out["a_scale"] = quant.calibrate_act_scale(x_sample, pct)
    return out


def analog_linear_apply(
    params: Params,
    x: jax.Array,
    cfg: AnalogConfig,
    *,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """DEPRECATED: use :func:`repro.api.apply_linear` (one-off layers) or
    ``repro.api.compile`` (models).  Bit-exact shim over the api front
    door - the implementation moved to :mod:`repro.api.program` (ISSUE 2).
    """
    import warnings

    warnings.warn(
        "analog_linear_apply is deprecated; use repro.api.apply_linear "
        "or repro.api.compile",
        DeprecationWarning, stacklevel=2,
    )
    from repro.api.program import apply_linear

    return apply_linear(params, x, cfg, key=key)
