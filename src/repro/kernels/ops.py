"""Jitted public wrappers around the Pallas kernels with automatic
platform dispatch and a custom-VJP HIL gradient.

This is the one module that chooses how an analog VMM is computed: a
Pallas kernel or its jnp oracle in :mod:`repro.kernels.ref`, by the
``use_pallas`` argument every wrapper takes (:func:`resolve_pallas`;
``None`` is the platform's choice).

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
from repro.distributed import sharding as shd
from repro.kernels import ref as ref_lib
from repro.kernels.analog_mvm import (
    analog_mvm_pallas,
    analog_mvm_split_pallas,
    expert_mvm_pallas,
)
from repro.kernels import analog_plan
from repro.kernels.analog_plan import analog_plan_pallas
from repro.kernels.preproc import maxmin_pool_2d_pallas, preprocess_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_pallas(use_pallas: Optional[bool] = None) -> bool:
    """Whether a wrapper runs its Pallas kernel: ``None`` takes the
    platform's choice (Pallas on TPU, the jnp oracle elsewhere); a bool
    forces one (``True`` on CPU runs the kernel interpreted)."""
    return _on_tpu() if use_pallas is None else use_pallas


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
    if resolve_pallas(use_pallas):
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
    # Weis et al.).
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
    jax.custom_vjp, nondiff_argnums=(5, 6, 7)
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
) -> jax.Array:
    """Signed-split analog VMM ``mvm(a_pos) - mvm(a_neg)`` as ONE
    dispatch: the Pallas split kernel streams each weight tile once for
    both passes; the jnp path is the two-pass oracle."""
    if resolve_pallas(use_pallas):
        return _column_parallel(
            functools.partial(analog_mvm_split_pallas, chunk_rows=chunk_rows,
                              faithful=faithful, interpret=_interpret()),
            (a_pos, a_neg), w_eff, gain, chunk_offset,
            a_pos.shape[1] // chunk_rows,
        )
    return ref_lib.analog_mvm_split_ref(
        a_pos, a_neg, w_eff, gain, chunk_offset,
        chunk_rows=chunk_rows, faithful=faithful,
    )


def expert_mvm(
    a_pos: jax.Array,
    a_neg: jax.Array,
    w_eff: jax.Array,
    gain: jax.Array,
    chunk_offset: jax.Array,
    tile_expert: jax.Array,
    live_tiles: jax.Array,
    *,
    block_m: int,
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """Signed-split analog VMM of expert-sorted rows: rows
    ``[i * block_m, (i + 1) * block_m)`` run through expert
    ``tile_expert[i]`` of ``w_eff [E, K, N]`` (``gain [E, N]``,
    ``chunk_offset [E, C, N]``).  Rows of tiles past ``live_tiles[0]``
    are unspecified.  On the Pallas path one grouped kernel
    (:func:`repro.kernels.analog_mvm.expert_mvm_pallas`); the jnp path
    runs every expert over every row and keeps each row's own - the
    oracle, for small shapes."""
    if resolve_pallas(use_pallas):
        return expert_mvm_pallas(
            a_pos, a_neg, w_eff, gain, chunk_offset, tile_expert,
            live_tiles, chunk_rows=chunk_rows, faithful=faithful,
            block_m=block_m, interpret=_interpret(),
        )
    row_expert = jnp.repeat(tile_expert, block_m)
    y = jnp.zeros((a_pos.shape[0], w_eff.shape[-1]), jnp.float32)
    for e in range(w_eff.shape[0]):
        ye = ref_lib.analog_mvm_split_ref(
            a_pos, a_neg, w_eff[e], gain[e], chunk_offset[e],
            chunk_rows=chunk_rows, faithful=faithful)
        y = jnp.where((row_expert == e)[:, None], ye, y)
    return y


def _analog_mvm_split_fwd(a_pos, a_neg, w_eff, gain, chunk_offset,
                          chunk_rows, faithful, use_pallas):
    y = analog_mvm_split(a_pos, a_neg, w_eff, gain, chunk_offset,
                         chunk_rows, faithful, use_pallas)
    return y, (a_pos, a_neg, w_eff, gain, chunk_offset)


def _analog_mvm_split_bwd(chunk_rows, faithful, use_pallas, res, g):
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
    if resolve_pallas(use_pallas):
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
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if resolve_pallas(use_pallas):
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
