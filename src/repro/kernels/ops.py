"""Jitted public wrappers around the Pallas kernels with automatic
platform dispatch and a custom-VJP HIL gradient.

- On TPU the Mosaic kernels run natively.
- On CPU ``interpret=True`` executes the kernel bodies in Python for
  bit-level validation against :mod:`repro.kernels.ref`.  No other
  backend runs them (:func:`_interpret`).
- Under a mesh of several devices the VMM kernels split their rows over
  the batch axes and their output columns over ``model``
  (:func:`_column_parallel`).
- ``analog_mvm`` carries the hardware-in-the-loop gradient (paper §III-B):
  forward through the saturating kernel, backward through the straight-
  through linearization of the ref oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.hw import BSS2
from repro.core.quant import ANALOG_PRECISION
from repro.distributed import sharding as shd
from repro.kernels import ref as ref_lib
from repro.kernels.analog_mvm import analog_mvm_pallas, analog_mvm_split_pallas
from repro.kernels import analog_plan
from repro.kernels.analog_plan import analog_plan_pallas
from repro.kernels.preproc import maxmin_pool_2d_pallas, preprocess_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Whether the Pallas kernels run interpreted: natively (Mosaic) on
    TPU, interpreted on CPU for bit-level validation against the oracle.
    Any other backend raises: interpreting there would run the kernel
    bodies on the host and hide the device."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels run natively on TPU or interpreted on CPU; "
            f"the {backend!r} backend is neither"
        )
    return backend == "cpu"


def _column_parallel(kernel, acts, w_eff, gain, chunk_offset, n_chunks):
    """Call an analog VMM kernel ``kernel(*acts, w_eff, gain, offset)``,
    under the active mesh if there is one.  Mosaic kernels cannot be
    partitioned automatically, so on a mesh of several devices the call
    runs in ``shard_map`` over the axes :func:`repro.distributed.sharding.
    vmm_axes` names: rows over the batch axes, output columns over
    ``model``, the layout the plan leaves are placed in.  A column's
    per-chunk ADC codes and epilogue depend on that column alone, so the
    split is exact."""
    n = w_eff.shape[1]
    gain = jnp.broadcast_to(jnp.asarray(gain, jnp.float32), (n,))
    mesh = shd.get_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(*acts, w_eff, gain, chunk_offset)
    if chunk_offset is None:
        chunk_offset = jnp.zeros((n_chunks, n), jnp.float32)
    rows, cols = shd.vmm_axes(acts[0].shape[0], n)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(rows, None),) * len(acts) + (P(None, cols), P(cols),
                                                 P(None, cols)),
        out_specs=P(rows, cols), check_vma=False,
    )(*acts, w_eff, gain, chunk_offset)


def _mvm_chunk_scan(a_code, w_eff, gain, chunk_offset, chunk_rows):
    """Faithful chunked VMM with an O(M, N) live set: ``lax.scan`` over
    the row chunks, accumulating each chunk's clipped ADC codes, instead
    of materializing the oracle's full [M, C, N] per-chunk tensor (the
    fused split path doubles M, so that tensor is what made the fused
    jnp dispatch SLOWER than per-call at bench shapes).  Faithful-only:
    per-chunk ADC codes are integer-valued f32, so the scan's running
    sum is bit-exact against the oracle's ``sum(axis=1)`` under any
    order; fast mode sums pre-round reals, where accumulation order
    matters at the ulp, and keeps the oracle path."""
    m, k = a_code.shape
    n = w_eff.shape[1]
    assert k % chunk_rows == 0, (k, chunk_rows)
    c = k // chunk_rows
    a_c = jnp.moveaxis(
        a_code.reshape(m, c, chunk_rows).astype(jnp.float32), 1, 0
    )
    w_c = w_eff.reshape(c, chunk_rows, n).astype(jnp.float32)
    off = (jnp.zeros((c, 1), jnp.float32) if chunk_offset is None
           else chunk_offset.astype(jnp.float32))

    def step(acc, xs):
        a_i, w_i, o_i = xs
        v = jnp.einsum("mk,kn->mn", a_i, w_i, precision=ANALOG_PRECISION,
                       preferred_element_type=jnp.float32) * gain + o_i
        return acc + jnp.clip(jnp.round(v), BSS2.adc_min, BSS2.adc_max), None

    y, _ = jax.lax.scan(step, jnp.zeros((m, n), jnp.float32),
                        (a_c, w_c, off))
    return y


def _mvm_split_chunk_scan(a_pos, a_neg, w_eff, gain, chunk_offset,
                          chunk_rows):
    """Faithful fused-split VMM as one chunk scan: both passes share each
    weight chunk while it is live and their ADC codes subtract into a
    single [M, N] accumulator - no [2M, K] activation concat, no
    [2M, C, N] per-chunk tensor.  Per-pass arithmetic is identical to
    the two-pass oracle and the codes are integer-valued f32, so the
    per-chunk subtraction order is bit-exact against ``yp - yn``."""
    m, k = a_pos.shape
    n = w_eff.shape[1]
    assert k % chunk_rows == 0, (k, chunk_rows)
    c = k // chunk_rows
    a_p = jnp.moveaxis(
        a_pos.reshape(m, c, chunk_rows).astype(jnp.float32), 1, 0
    )
    a_n = jnp.moveaxis(
        a_neg.reshape(m, c, chunk_rows).astype(jnp.float32), 1, 0
    )
    w_c = w_eff.reshape(c, chunk_rows, n).astype(jnp.float32)
    off = (jnp.zeros((c, 1), jnp.float32) if chunk_offset is None
           else chunk_offset.astype(jnp.float32))

    def step(acc, xs):
        ap_i, an_i, w_i, o_i = xs
        vp = jnp.einsum("mk,kn->mn", ap_i, w_i, precision=ANALOG_PRECISION,
                        preferred_element_type=jnp.float32) * gain + o_i
        vn = jnp.einsum("mk,kn->mn", an_i, w_i, precision=ANALOG_PRECISION,
                        preferred_element_type=jnp.float32) * gain + o_i
        adc_p = jnp.clip(jnp.round(vp), BSS2.adc_min, BSS2.adc_max)
        adc_n = jnp.clip(jnp.round(vn), BSS2.adc_min, BSS2.adc_max)
        return acc + (adc_p - adc_n), None

    y, _ = jax.lax.scan(step, jnp.zeros((m, n), jnp.float32),
                        (a_p, a_n, w_c, off))
    return y


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6)
)
def analog_mvm(
    a_code: jax.Array,
    w_eff: jax.Array,
    gain: jax.Array,
    chunk_offset: Optional[jax.Array],
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """[M, K] x [K, N] chunked saturating analog VMM (forward = hardware)."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return _column_parallel(
            functools.partial(analog_mvm_pallas, chunk_rows=chunk_rows,
                              faithful=faithful, interpret=_interpret()),
            (a_code,), w_eff, gain, chunk_offset,
            a_code.shape[1] // chunk_rows,
        )
    return ref_lib.analog_mvm_ref(
        a_code, w_eff, gain, chunk_offset,
        chunk_rows=chunk_rows, faithful=faithful,
    )


def _analog_mvm_fwd(a_code, w_eff, gain, chunk_offset,
                    chunk_rows, faithful, use_pallas):
    y = analog_mvm(a_code, w_eff, gain, chunk_offset,
                   chunk_rows, faithful, use_pallas)
    return y, (a_code, w_eff, gain, chunk_offset)


def _analog_mvm_bwd(chunk_rows, faithful, use_pallas, res, g):
    # HIL gradient: treat the hardware op as y ~= gain * (a @ w) and
    # backpropagate through that linearization (STE across round/clip).
    # The gain is frozen calibration state (paper §III-B: only the float
    # master weights train; gain/offsets come from per-layer calibration,
    # Weis et al.) - same semantics as core.analog._faithful_mm_bwd.
    a_code, w_eff, gain, chunk_offset = res
    g_scaled = g * gain                      # [M, N] * [N]
    da = g_scaled @ w_eff.T
    dw = a_code.T @ g_scaled
    dgain = jnp.zeros_like(gain)
    # fixed-pattern offsets are frozen hardware buffers, not trained
    d_off = None if chunk_offset is None else jnp.zeros_like(chunk_offset)
    return da, dw, dgain, d_off


analog_mvm.defvjp(_analog_mvm_fwd, _analog_mvm_bwd)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8)
)
def analog_mvm_split(
    a_pos: jax.Array,
    a_neg: jax.Array,
    w_eff: jax.Array,
    gain: jax.Array,
    chunk_offset: Optional[jax.Array],
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
    use_pallas: Optional[bool] = None,
    fused: bool = True,
) -> jax.Array:
    """Signed-split analog VMM ``mvm(a_pos) - mvm(a_neg)`` as ONE dispatch.

    ``fused=True`` (default) shares the weight tiles between the two
    passes: on the Pallas path via the single-grid split kernel, on the
    jnp path (faithful) via a chunk scan that subtracts the two passes'
    integer ADC codes in place (:func:`_mvm_split_chunk_scan` - the fix
    for the fused dispatch benching SLOWER than per-call).  The code-
    domain arithmetic is exact under any accumulation order; per-chunk
    pre-round products carry the usual fp32 contraction-order
    sensitivity at exact round boundaries (same caveat the Pallas kernel
    documents), which the pinned bit-exactness tests bound.  Fast mode
    sums pre-round reals and keeps the stacked-batch oracle matmul,
    bit-exact against the two-pass oracle by construction.
    """
    use = _on_tpu() if use_pallas is None else use_pallas
    if not fused:
        return ref_lib.analog_mvm_split_ref(
            a_pos, a_neg, w_eff, gain, chunk_offset,
            chunk_rows=chunk_rows, faithful=faithful,
        )
    if use:
        return _column_parallel(
            functools.partial(analog_mvm_split_pallas, chunk_rows=chunk_rows,
                              faithful=faithful, interpret=_interpret()),
            (a_pos, a_neg), w_eff, gain, chunk_offset,
            a_pos.shape[1] // chunk_rows,
        )
    # fused jnp path, faithful: stream the chunks through a scan that
    # shares each weight chunk between the pos/neg passes and subtracts
    # their integer ADC codes in place (bit-exact vs the two-pass
    # oracle; see _mvm_split_chunk_scan).  Fast mode sums pre-round
    # reals - accumulation order matters at the ulp there - and keeps
    # the oracle's stacked [2M, K] chunked matmul.
    if faithful:
        return _mvm_split_chunk_scan(a_pos, a_neg, w_eff, gain,
                                     chunk_offset, chunk_rows)
    m = a_pos.shape[0]
    y2 = ref_lib.analog_mvm_ref(
        jnp.concatenate([a_pos, a_neg], axis=0), w_eff, gain, chunk_offset,
        chunk_rows=chunk_rows, faithful=faithful,
    )
    return y2[:m] - y2[m:]


def _analog_mvm_split_fwd(a_pos, a_neg, w_eff, gain, chunk_offset,
                          chunk_rows, faithful, use_pallas, fused):
    y = analog_mvm_split(a_pos, a_neg, w_eff, gain, chunk_offset,
                         chunk_rows, faithful, use_pallas, fused)
    return y, (a_pos, a_neg, w_eff, gain, chunk_offset)


def _analog_mvm_split_bwd(chunk_rows, faithful, use_pallas, fused, res, g):
    # HIL linearization of the split pair: y ~= gain * ((a_pos - a_neg) @ w)
    # with frozen gain/offset calibration state.
    a_pos, a_neg, w_eff, gain, chunk_offset = res
    g_scaled = g * gain
    da = g_scaled @ w_eff.T
    dw = (a_pos - a_neg).T @ g_scaled
    dgain = jnp.zeros_like(gain)
    d_off = None if chunk_offset is None else jnp.zeros_like(chunk_offset)
    return da, -da, dw, dgain, d_off


analog_mvm_split.defvjp(_analog_mvm_split_fwd, _analog_mvm_split_bwd)


def analog_mvm_infer(
    a_pos: jax.Array,
    a_neg: Optional[jax.Array],
    w_eff: jax.Array,
    gain: jax.Array,
    chunk_offset: Optional[jax.Array],
    *,
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
    use_pallas: Optional[bool] = None,
    epilogue=None,
) -> jax.Array:
    """Inference-only analog VMM with the ADC epilogue fused INTO the
    kernel (plan executor hot path; no custom VJP - the differentiable
    path applies the epilogue as elementwise STE ops instead, which is
    bit-identical in value).  ``a_neg=None`` selects the unsigned
    single-pass kernel, otherwise the fused signed-split kernel."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        kw = dict(chunk_rows=chunk_rows, faithful=faithful,
                  epilogue=epilogue, interpret=_interpret())
        kernel, acts = ((analog_mvm_pallas, (a_pos,)) if a_neg is None
                        else (analog_mvm_split_pallas, (a_pos, a_neg)))
        return _column_parallel(
            functools.partial(kernel, **kw), acts, w_eff, gain,
            chunk_offset, a_pos.shape[1] // chunk_rows,
        )
    if a_neg is None:
        y = (_mvm_chunk_scan(a_pos, w_eff, gain, chunk_offset, chunk_rows)
             if faithful else
             ref_lib.analog_mvm_ref(a_pos, w_eff, gain, chunk_offset,
                                    chunk_rows=chunk_rows,
                                    faithful=faithful))
    elif faithful:
        y = _mvm_split_chunk_scan(a_pos, a_neg, w_eff, gain,
                                  chunk_offset, chunk_rows)
    else:
        m = a_pos.shape[0]
        y2 = ref_lib.analog_mvm_ref(
            jnp.concatenate([a_pos, a_neg], axis=0), w_eff, gain,
            chunk_offset, chunk_rows=chunk_rows, faithful=faithful,
        )
        y = y2[:m] - y2[m:]
    return ref_lib.adc_epilogue_ref(y, epilogue)


def analog_plan_codes(
    x_in: jax.Array,
    w_cat: jax.Array,
    gain_all: jax.Array,
    off_cat: jax.Array,
    *,
    schedule,
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
    use_pallas: Optional[bool] = None,
    block_b: Optional[int] = None,
    extras=None,
    block=None,
) -> jax.Array:
    """Whole-plan megakernel dispatch: one packed layer chain, ONE kernel
    launch (plan executor megakernel hot path).

    On the Pallas path the entire chain runs inside a single
    ``pallas_call`` with VMEM-resident inter-layer activations; the jnp
    path traces the identical chain as one fused function
    (:func:`repro.kernels.ref.analog_plan_ref`).  ``extras`` carries the
    packed float-glue leaves ``(deq, bias, enc, ln)`` for chains with
    float-domain hand-offs (None for pure code-domain chains); ``block``
    is the static :class:`repro.kernels.analog_plan.BlockMeta` geometry
    of a fused attention+MLP block.  Returns the final layer's raw
    accumulated ADC codes ``[B * m_last, n_last]`` (hand-off "raw") or
    the glued float block output (hand-off "res_out").

    Differentiable on BOTH paths: the custom VJP backpropagates through
    the STE/HIL reference chain (frozen gain/offsets, linearized ADC,
    STE in-kernel encoders - the same gradients the layer-by-layer
    replay produces), so compiling a chain inside a differentiated train
    step keeps the HIL contract even when the forward ran the Pallas
    megakernel.
    """
    return _plan_codes(x_in, w_cat, gain_all, off_cat, extras, schedule,
                       chunk_rows, faithful, use_pallas, block_b, block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _plan_codes(x_in, w_cat, gain_all, off_cat, extras, schedule,
                chunk_rows, faithful, use_pallas, block_b, block):
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        b = x_in.shape[0] // schedule[0].m_mult
        # bounded ROWS per grid step (block_b * m_mult0), not batch
        # elements: keeps the x/scratch working set flat across chain
        # geometries (the small-batch im2col grid/scratch fix)
        bb = block_b if block_b is not None else analog_plan.default_block_b(
            b, schedule[0].m_mult)
        deq = bias = enc = ln = None
        if extras is not None:
            deq, bias, enc, ln = extras
        return analog_plan_pallas(
            x_in, w_cat, gain_all, off_cat, deq, bias, enc, ln,
            schedule=schedule, chunk_rows=chunk_rows, faithful=faithful,
            block_b=bb, block=block, interpret=_interpret(),
        )
    return ref_lib.analog_plan_ref(
        x_in, w_cat, gain_all, off_cat, schedule,
        chunk_rows=chunk_rows, faithful=faithful,
        extras=extras, block=block,
    )


def _plan_codes_fwd(x_in, w_cat, gain_all, off_cat, extras, schedule,
                    chunk_rows, faithful, use_pallas, block_b, block):
    y = _plan_codes(x_in, w_cat, gain_all, off_cat, extras, schedule,
                    chunk_rows, faithful, use_pallas, block_b, block)
    return y, (x_in, w_cat, gain_all, off_cat, extras)


def _plan_codes_bwd(schedule, chunk_rows, faithful, use_pallas, block_b,
                    block, res, g):
    # HIL gradient: differentiate the STE reference chain (gain and
    # offsets are frozen calibration state inside analog_plan_ref; the
    # float-glue leaves in ``extras`` receive real gradients, like the
    # per-layer dequantization does)
    x_in, w_cat, gain_all, off_cat, extras = res
    _, vjp = jax.vjp(
        lambda x_, w_, g_, o_, e_: ref_lib.analog_plan_ref(
            x_, w_, g_, o_, schedule,
            chunk_rows=chunk_rows, faithful=faithful,
            extras=e_, block=block,
        ),
        x_in, w_cat, gain_all, off_cat, extras,
    )
    return vjp(g)


_plan_codes.defvjp(_plan_codes_fwd, _plan_codes_bwd)


def maxmin_pool(x: jax.Array, window: int = 32,
                use_pallas: Optional[bool] = None) -> jax.Array:
    """[..., T] -> [..., T/window] max-min pooling (preprocessing chain)."""
    use = _on_tpu() if use_pallas is None else use_pallas
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if use:
        y = maxmin_pool_2d_pallas(x2, window=window, interpret=_interpret())
    else:
        y = ref_lib.maxmin_pool_ref(x2, window=window)
    return y.reshape(shape[:-1] + (shape[-1] // window,))


def preprocess_codes(raw: jax.Array, window: int, quant_shift: int
                     ) -> jax.Array:
    """[..., C, T] raw samples -> [..., C, (T-1)//window] 5-bit codes:
    the whole pre-processing chain in the fused Pallas kernel."""
    shape = raw.shape
    y = preprocess_pallas(raw.reshape((-1,) + shape[-2:]), window=window,
                          quant_shift=quant_shift, interpret=_interpret())
    return y.reshape(shape[:-1] + y.shape[-1:])
