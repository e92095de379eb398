"""Pallas TPU kernel for the BSS-2 analog VMM emulation.

This is the compute hot-spot of the framework: every analog-mapped linear
layer reduces to many  ``[M, K] x [K, N]``  chunked saturating matmuls.  The
kernel implements the per-128-row-chunk ADC semantics *inside* the MXU loop,
so the faithful mode costs one extra round/clip/add per (bm, bn) tile per
chunk instead of materializing ``[M, C, N]`` partials in HBM like the naive
lowering does (memory-roofline win: the chunk axis never leaves VMEM).

TPU mapping decisions (hw-codesign):
- bk = 128 (the BSS-2 signed-row chunk IS the MXU contraction tile - the
  paper's geometry is natively TPU-friendly); bm and bn are chosen from
  the shapes (:func:`block_rows`, :func:`block_cols`) so the blocks fit
  VMEM, few rows are not padded to a large block and no columns are
  padded where a multiple of 128 divides N.
- each chunk's contraction is :func:`code_dot`: three bf16 MXU passes,
  the activation codes against three bf16 parts of the fp32 effective
  weights, where an fp32 dot at full precision takes six.  Activations
  must be integer codes of magnitude at most 256 (the kernels see 5-bit
  codes), which bf16 holds exactly; the weights keep their fixed-pattern
  and calibration gains to the last fp32 bit, so the result is the fp32
  dot's, bit for bit (PERF.md).
- the chunk/grid-K axis is the innermost ("arbitrary") grid dimension and
  accumulates into an fp32 VMEM scratch; output is written once on the last
  chunk step.

Validated against :func:`repro.kernels.ref.analog_mvm_ref` in interpret mode
(CPU) over shape/dtype sweeps - see tests/test_kernels.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import BSS2
from repro.core.quant import ANALOG_PRECISION
from repro.obs import metrics as _obs_metrics


def mxu_dot(a: jax.Array, w: jax.Array) -> jax.Array:
    """One chunk's MXU dot: fp32 operands contracted at full fp32
    precision.  Mosaic's default for fp32 operands may round them to bf16,
    which moves ADC codes off the fp32 oracle."""
    return jnp.dot(a, w, preferred_element_type=jnp.float32,
                   precision=ANALOG_PRECISION)


def _truncate(x: jax.Array) -> jax.Array:
    """``x`` cut to its leading 8 significant bits (a bf16 number)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def split_bf16x3(w: jax.Array):
    """``(hi, mid, lo)``: the fp32 ``w`` as three bf16 parts whose sum is
    ``w`` exactly, each cut by truncation: ``hi`` is ``w``'s leading 8
    significant bits, ``mid`` the leading 8 of the rest ``w - hi``, ``lo``
    what remains (at most 8 significant bits).  These are the parts an
    fp32 MXU dot at full precision latches (probed bit for bit on a v5e,
    PERF.md)."""
    w = w.astype(jnp.float32)
    hi = _truncate(w)
    rest = w - hi
    mid = _truncate(rest)
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, rest - mid))


def code_dot(a: jax.Array, parts) -> jax.Array:
    """One chunk's contraction of activation codes ``a`` with fp32
    weights, given as their :func:`split_bf16x3` ``parts``, in three bf16
    MXU passes added as an fp32 dot at full precision adds its passes:
    ``(a·lo + a·mid) + a·hi``.  (Written with ``a·hi`` first, which the
    commutative last add allows, Mosaic pops all three into one running
    sum.)  ``a`` must hold integers of magnitude at most 256, which bf16
    holds exactly: the three passes that dot spends on the activation's
    lower bits then multiply by zero, and the result is that dot's, bit
    for bit."""
    _obs_metrics.counter("analog.mvm.bf16x3_dots").inc()
    a = a.astype(jnp.bfloat16)

    def one(part):
        return jnp.dot(a, part, preferred_element_type=jnp.float32)

    hi, mid, lo = parts
    return one(hi) + (one(lo) + one(mid))


def _offset_rows(chunk_offset, n_chunks: int, n: int, pn: int) -> jax.Array:
    """``[C, N]`` chunk offsets (or None = zeros) as ``[C, 1, N + pn]``:
    each chunk's offset row is its own ``(1, N)`` slab, a block shape
    Mosaic's (8, 128) tiling accepts for any C (a ``(1, block_n)`` block
    over ``[C, N]`` breaks the rule whenever C > 1)."""
    if chunk_offset is None:
        return jnp.zeros((n_chunks, 1, n + pn), jnp.float32)
    chunk_offset = jnp.asarray(chunk_offset, jnp.float32)
    if pn:
        chunk_offset = jnp.pad(chunk_offset, ((0, 0), (0, pn)))
    return chunk_offset[:, None, :]


def _offset_spec(block_n: int):
    # chunk c's offset row, squeezed to (1, block_n) in the kernel
    return pl.BlockSpec((None, 1, block_n), lambda i, j, c: (c, 0, j))


# the most output elements one grid step holds: (512, 1024) fits the two
# fp32 accumulators, the double-buffered output block and the operand
# blocks in the default scoped VMEM
_BLOCK_ELEMS = 512 * 1024


def block_rows(m: int) -> int:
    """The row block for ``m`` rows: 512, or ``m`` rounded up to whole
    sublanes when fewer - a decode step's 4 rows pad to 8, not 512."""
    return min(512, -(-m // 8) * 8)


def block_cols(n: int, block_m: int) -> int:
    """The column block for ``n`` columns at ``block_m`` rows: all of
    ``n`` (in whole lanes) if it fits, else the largest multiple of 128
    that divides it, so no column is padded, unless that is under half
    the largest block that fits, which is then taken."""
    cap = min(2048, _BLOCK_ELEMS // block_m // 128 * 128)
    n128 = -(-n // 128) * 128
    if n128 <= cap:
        return n128
    div = max(b for b in range(128, cap + 1, 128) if n128 % b == 0)
    return div if 2 * div >= cap else cap


def _apply_epilogue(acc, epilogue):
    """ADC epilogue (paper §II-A), applied to the digitally accumulated ADC
    codes before they leave VMEM: ReLU at the readout followed by a bitwise
    right-shift requantization onto the 5-bit input-activation range.  The
    next stacked analog layer consumes the result directly as event codes,
    so the inter-layer glue never touches HBM as floats."""
    if epilogue is None:
        return acc
    kind, shift = epilogue
    if kind != "relu_shift":
        raise ValueError(f"unknown epilogue {epilogue!r}")
    acc = jnp.maximum(acc, 0.0)
    acc = jnp.floor(acc / float(1 << shift))
    return jnp.clip(acc, 0.0, float(BSS2.a_max))


def _kernel(a_ref, w_ref, gain_ref, off_ref, o_ref, acc_ref, *,
            n_chunks: int, faithful: bool, epilogue=None):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v = code_dot(a_ref[...], split_bf16x3(w_ref[...]))
    v = v * gain_ref[...] + off_ref[...]
    if faithful:
        # 8-bit saturating ADC per chunk, digital accumulation
        v = jnp.clip(jnp.round(v), float(BSS2.adc_min), float(BSS2.adc_max))
    acc_ref[...] += v

    @pl.when(c == n_chunks - 1)
    def _done():
        acc = acc_ref[...]
        if not faithful:
            lo = float(BSS2.adc_min) * n_chunks
            hi = float(BSS2.adc_max) * n_chunks
            acc = jnp.clip(jnp.round(acc), lo, hi)
        o_ref[...] = _apply_epilogue(acc, epilogue)


@functools.partial(
    jax.jit,
    static_argnames=(
        "chunk_rows", "faithful", "block_m", "block_n", "interpret",
        "epilogue",
    ),
)
def analog_mvm_pallas(
    a_code: jax.Array,                    # [M, K]
    w_eff: jax.Array,                     # [K, N]
    gain: jax.Array,                      # [N]
    chunk_offset: Optional[jax.Array],    # [C, N] or None
    *,
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
    block_m: Optional[int] = None,        # default: block_rows(M)
    block_n: Optional[int] = None,        # default: block_cols(N, block_m)
    interpret: bool = False,
    epilogue=None,                        # None | ("relu_shift", shift)
) -> jax.Array:
    """Chunked saturating analog VMM; bit-exact vs the fp32 oracle in
    interpret mode.  ``a_code`` holds integer codes of magnitude at most
    256, the contract of :func:`code_dot`."""
    m, k = a_code.shape
    k2, n = w_eff.shape
    assert k == k2, (k, k2)
    assert k % chunk_rows == 0, (k, chunk_rows)
    n_chunks = k // chunk_rows
    block_m = block_m or block_rows(m)
    block_n = block_n or block_cols(n, block_m)

    # pad M and N to block multiples (K is already chunk-aligned)
    pm = (-m) % block_m
    pn = (-n) % block_n
    if pm:
        a_code = jnp.pad(a_code, ((0, pm), (0, 0)))
    if pn:
        w_eff = jnp.pad(w_eff, ((0, 0), (0, pn)))
    # [1, N] (a 2-D row block; 1-D blocks mismatch XLA's tiling for N > 1024)
    gain = jnp.broadcast_to(jnp.asarray(gain, jnp.float32), (n,))
    gain = jnp.pad(gain, (0, pn))[None, :]
    chunk_offset = _offset_rows(chunk_offset, n_chunks, n, pn)
    mp, np_ = m + pm, n + pn

    grid = (mp // block_m, np_ // block_n, n_chunks)
    out = pl.pallas_call(
        functools.partial(
            _kernel, n_chunks=n_chunks, faithful=faithful,
            epilogue=epilogue,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, chunk_rows), lambda i, j, c: (i, c)),
            pl.BlockSpec((chunk_rows, block_n), lambda i, j, c: (c, j)),
            pl.BlockSpec((1, block_n), lambda i, j, c: (0, j)),
            _offset_spec(block_n),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[
            # fp32 accumulator lives in VMEM across the chunk loop
            pltpu.VMEM((block_m, block_n), jnp.float32)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a_code.astype(jnp.float32), w_eff.astype(jnp.float32), gain, chunk_offset)
    return out[:m, :n]


# --------------------------------------------------------------------------
# fused signed-split kernel
# --------------------------------------------------------------------------
def _split_kernel(ap_ref, an_ref, w_ref, gain_ref, off_ref, o_ref,
                  accp_ref, accn_ref, *, n_chunks: int, faithful: bool,
                  epilogue=None):
    """One grid pass over the shared weight tiles evaluates BOTH analog
    passes of the signed-split encoding (paper §II-A: positive and negative
    activation parts on the same synapse columns).  Each (bm, bn, c) step
    streams the weight tile from HBM once, splits it into its bf16 parts
    once and contracts both passes against them - halving weight traffic
    and kernel dispatches vs. two independent ``analog_mvm`` calls.  ADC
    saturation is applied to each pass independently (each is a physical
    analog run), then the difference is formed digitally on the last
    chunk step."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        accp_ref[...] = jnp.zeros_like(accp_ref)
        accn_ref[...] = jnp.zeros_like(accn_ref)

    w = split_bf16x3(w_ref[...])
    gain = gain_ref[...]
    off = off_ref[...]
    vp = code_dot(ap_ref[...], w) * gain + off
    vn = code_dot(an_ref[...], w) * gain + off
    if faithful:
        lo, hi = float(BSS2.adc_min), float(BSS2.adc_max)
        vp = jnp.clip(jnp.round(vp), lo, hi)
        vn = jnp.clip(jnp.round(vn), lo, hi)
    accp_ref[...] += vp
    accn_ref[...] += vn

    @pl.when(c == n_chunks - 1)
    def _done():
        accp, accn = accp_ref[...], accn_ref[...]
        if not faithful:
            lo = float(BSS2.adc_min) * n_chunks
            hi = float(BSS2.adc_max) * n_chunks
            accp = jnp.clip(jnp.round(accp), lo, hi)
            accn = jnp.clip(jnp.round(accn), lo, hi)
        o_ref[...] = _apply_epilogue(accp - accn, epilogue)


@functools.partial(
    jax.jit,
    static_argnames=(
        "chunk_rows", "faithful", "block_m", "block_n", "interpret",
        "epilogue",
    ),
)
def analog_mvm_split_pallas(
    a_pos: jax.Array,                     # [M, K] codes of max(x, 0)
    a_neg: jax.Array,                     # [M, K] codes of max(-x, 0)
    w_eff: jax.Array,                     # [K, N]
    gain: jax.Array,                      # [N]
    chunk_offset: Optional[jax.Array],    # [C, N] or None
    *,
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
    block_m: Optional[int] = None,        # default: block_rows(M)
    block_n: Optional[int] = None,        # default: block_cols(N, block_m)
    interpret: bool = False,
    epilogue=None,                        # None | ("relu_shift", shift)
) -> jax.Array:
    """Fused signed-split analog VMM: ``mvm(a_pos) - mvm(a_neg)`` in one
    kernel launch with single weight streaming.  Bit-exact (fp32) against
    the two-pass oracle because per-pass arithmetic is unchanged - only the
    tile schedule is shared (tested in tests/test_exec.py).  Both passes'
    codes are integers of magnitude at most 256 (:func:`code_dot`)."""
    m, k = a_pos.shape
    assert a_neg.shape == (m, k), (a_neg.shape, a_pos.shape)
    k2, n = w_eff.shape
    assert k == k2, (k, k2)
    assert k % chunk_rows == 0, (k, chunk_rows)
    n_chunks = k // chunk_rows
    block_m = block_m or block_rows(m)
    block_n = block_n or block_cols(n, block_m)

    pm = (-m) % block_m
    pn = (-n) % block_n
    if pm:
        a_pos = jnp.pad(a_pos, ((0, pm), (0, 0)))
        a_neg = jnp.pad(a_neg, ((0, pm), (0, 0)))
    if pn:
        w_eff = jnp.pad(w_eff, ((0, 0), (0, pn)))
    # [1, N] (a 2-D row block; 1-D blocks mismatch XLA's tiling for N > 1024)
    gain = jnp.broadcast_to(jnp.asarray(gain, jnp.float32), (n,))
    gain = jnp.pad(gain, (0, pn))[None, :]
    chunk_offset = _offset_rows(chunk_offset, n_chunks, n, pn)
    mp, np_ = m + pm, n + pn

    grid = (mp // block_m, np_ // block_n, n_chunks)
    out = pl.pallas_call(
        functools.partial(
            _split_kernel, n_chunks=n_chunks, faithful=faithful,
            epilogue=epilogue,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, chunk_rows), lambda i, j, c: (i, c)),
            pl.BlockSpec((block_m, chunk_rows), lambda i, j, c: (i, c)),
            pl.BlockSpec((chunk_rows, block_n), lambda i, j, c: (c, j)),
            pl.BlockSpec((1, block_n), lambda i, j, c: (0, j)),
            _offset_spec(block_n),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_m, block_n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        a_pos.astype(jnp.float32), a_neg.astype(jnp.float32),
        w_eff.astype(jnp.float32), gain, chunk_offset,
    )
    return out[:m, :n]


# --------------------------------------------------------------------------
# grouped signed-split kernel (held experts)
# --------------------------------------------------------------------------
def _grouped_kernel(te_ref, live_ref, ap_ref, an_ref, w_ref, gain_ref,
                    off_ref, o_ref, accp_ref, accn_ref, *, n_chunks: int,
                    faithful: bool):
    """:func:`_split_kernel` over one row tile of one expert's group; a
    tile past the live ones does nothing."""
    del te_ref
    i, c = pl.program_id(0), pl.program_id(2)

    @pl.when(i < live_ref[0])
    def _live():
        @pl.when(c == 0)
        def _init():
            accp_ref[...] = jnp.zeros_like(accp_ref)
            accn_ref[...] = jnp.zeros_like(accn_ref)

        w = split_bf16x3(w_ref[...])
        gain = gain_ref[...]
        off = off_ref[...]
        vp = code_dot(ap_ref[...], w) * gain + off
        vn = code_dot(an_ref[...], w) * gain + off
        if faithful:
            lo, hi = float(BSS2.adc_min), float(BSS2.adc_max)
            vp = jnp.clip(jnp.round(vp), lo, hi)
            vn = jnp.clip(jnp.round(vn), lo, hi)
        accp_ref[...] += vp
        accn_ref[...] += vn

        @pl.when(c == n_chunks - 1)
        def _done():
            accp, accn = accp_ref[...], accn_ref[...]
            if not faithful:
                lo = float(BSS2.adc_min) * n_chunks
                hi = float(BSS2.adc_max) * n_chunks
                accp = jnp.clip(jnp.round(accp), lo, hi)
                accn = jnp.clip(jnp.round(accn), lo, hi)
            o_ref[...] = accp - accn


@functools.partial(
    jax.jit,
    static_argnames=("chunk_rows", "faithful", "block_m", "block_n",
                     "interpret"),
)
def expert_mvm_pallas(
    a_pos: jax.Array,                     # [G * block_m, K] grouped rows
    a_neg: jax.Array,                     # [G * block_m, K]
    w_eff: jax.Array,                     # [E, K, N] held experts
    gain: jax.Array,                      # [E, N]
    chunk_offset: jax.Array,              # [E, C, N]
    tile_expert: jax.Array,               # [G] int32: each tile's expert
    live_tiles: jax.Array,                # [1] int32: tiles that hold rows
    *,
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
    block_m: int = 128,
    block_n: Optional[int] = None,        # default: block_cols(N, block_m)
    interpret: bool = False,
) -> jax.Array:
    """Grouped signed-split analog VMM: row tile ``i`` of the
    expert-sorted rows runs through expert ``tile_expert[i]``'s weights,
    with the per-chunk ADC of :func:`analog_mvm_split_pallas`.  The
    expert ids and the number of live tiles reach the kernel by scalar
    prefetch, so the weight blocks follow the groups and a tile past
    ``live_tiles`` fetches nothing new and computes nothing: its output
    rows are left unwritten.  The chunking is along K, so the zero rows
    that pad each group to whole tiles change no live row.  Both passes'
    codes are integers of magnitude at most 256 (:func:`code_dot`)."""
    r, k = a_pos.shape
    e, k2, n = w_eff.shape
    assert k == k2 and k % chunk_rows == 0, (k, k2, chunk_rows)
    assert r % block_m == 0, (r, block_m)
    g = r // block_m
    n_chunks = k // chunk_rows
    block_n = block_n or block_cols(n, block_m)
    pn = (-n) % block_n
    if pn:
        w_eff = jnp.pad(w_eff, ((0, 0), (0, 0), (0, pn)))
    gain = jnp.pad(jnp.asarray(gain, jnp.float32), ((0, 0), (0, pn)))
    off = jnp.pad(jnp.asarray(chunk_offset, jnp.float32),
                  ((0, 0), (0, 0), (0, pn)))[:, :, None, :]
    np_ = n + pn
    nj = np_ // block_n

    def last(lv):
        return jnp.maximum(lv[0], 1) - 1

    def a_map(i, j, c, te, lv):
        on = i < lv[0]
        return jnp.where(on, i, last(lv)), jnp.where(on, c, n_chunks - 1)

    def w_map(i, j, c, te, lv):
        on = i < lv[0]
        return (jnp.where(on, te[i], te[last(lv)]),
                jnp.where(on, c, n_chunks - 1), jnp.where(on, j, nj - 1))

    def gain_map(i, j, c, te, lv):
        on = i < lv[0]
        return (jnp.where(on, te[i], te[last(lv)]), 0,
                jnp.where(on, j, nj - 1))

    def off_map(i, j, c, te, lv):
        on = i < lv[0]
        return (jnp.where(on, te[i], te[last(lv)]),
                jnp.where(on, c, n_chunks - 1), 0, jnp.where(on, j, nj - 1))

    def out_map(i, j, c, te, lv):
        # a dead tile parks on one spare tile past the end, so no live
        # output block is revisited or overwritten
        on = i < lv[0]
        return jnp.where(on, i, g), jnp.where(on, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g, nj, n_chunks),
        in_specs=[
            pl.BlockSpec((block_m, chunk_rows), a_map),
            pl.BlockSpec((block_m, chunk_rows), a_map),
            pl.BlockSpec((None, chunk_rows, block_n), w_map),
            pl.BlockSpec((None, 1, block_n), gain_map),
            pl.BlockSpec((None, None, 1, block_n), off_map),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), out_map),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_m, block_n), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, n_chunks=n_chunks,
                          faithful=faithful),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(((g + 1) * block_m, np_),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="expert_mvm_pallas",
    )(
        tile_expert.astype(jnp.int32), live_tiles.astype(jnp.int32),
        a_pos.astype(jnp.float32), a_neg.astype(jnp.float32),
        w_eff.astype(jnp.float32), gain[:, None, :], off,
    )
    return out[:r, :n]
