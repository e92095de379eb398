"""Whole-plan Pallas megakernel: one ``pallas_call`` per AnalogPlan.

The paper's headline figure - 276 us / 192 uJ per ECG sample (§IV) - comes
from the conv->fc1->fc2 CDNN running as ONE uninterrupted analog program on
the ASIC: inter-layer 5-bit activation codes are written by the SIMD CPU
straight back into the synapse drivers and never leave the chip (§II-A).
The per-layer executor in :mod:`repro.exec.run` already fuses the ADC
epilogue into each layer's kernel, but still issues one ``pallas_call``
per layer, bouncing the inter-layer activations through HBM.  This kernel
closes that gap: it executes an entire packed layer chain inside one
kernel launch.

Hand-off domains (the ``MegaLayerMeta.handoff`` tag, baked at lower time
by :func:`repro.exec.lower.pack_megakernel`):

- ``"codes"``  - the classic code-domain hand-off: ReLU + right-shift
  requantization to 5-bit codes at the ADC (paper §II-A); the next layer
  consumes the codes directly.
- ``"relu"``   - a float-domain hand-off: the accumulated ADC result is
  dequantized IN-KERNEL (precomputed ``deq = a_scale * w_scale / gain``
  rows + bias), passed through ReLU, and re-encoded at the next layer's
  baked static activation LSB (unsigned or signed-split codes).  This is
  what lifts the old code-domain-only restriction: a mixed chain of
  relu_shift and float-glue layers still runs as ONE ``pallas_call``.
- ``"attn"`` / ``"res_ln"`` / ``"swiglu"`` / ``"res_out"`` - the
  transformer-block glue (fused QKV -> RoPE + causal attention,
  residual-add + RMSNorm, SwiGLU, residual output), so a whole
  attention+MLP block executes as a single dispatch (5 -> 1).  The
  attention math is the SAME function the model path uses
  (:func:`repro.models.attention.prefill_attention_glue`), so parity is
  by construction.
- ``"raw"``    - final layer: raw accumulated ADC codes leave the kernel
  and are dequantized outside (the legacy epilogue == "none" hand-off).

TPU mapping:
- the grid runs over blocks of the *batch* only (rows are independent end
  to end, so each grid step owns its slice of every layer); weights, gains
  and chunk offsets are packed once at lower time into row-concatenated
  VMEM blocks whose index maps are constant - Mosaic keeps them resident
  across grid steps instead of re-streaming per layer,
- inter-layer activations (5-bit codes OR fp32 float features) round-trip
  through a VMEM scratch buffer (the software mirror of the on-chip
  activation path); block plans carry a second scratch holding the fp32
  residual stream,
- ``flatten_out`` layers (the ECG conv->fc1 im2col hand-off) merge their
  position axis into the next layer's contraction axis by strided loads
  from the scratch buffer (:func:`_flatten_positions`).

The static layer schedule (:class:`MegaLayerMeta` tuple, plus the optional
:class:`BlockMeta` transformer-glue geometry) is baked at lower time; the
kernel body unrolls over it, so per-layer chunk counts, shifts, encodings
and flatten factors are compile-time constants.

Validated bit-exactly (fp32, interpret mode) against the layer-by-layer
plan replay - see tests/test_kernels.py, tests/test_exec.py and
tests/test_megakernel_float.py.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw import BSS2
from repro.kernels.analog_mvm import mxu_dot

# Default rows-per-grid-step budget of the batch-only grid.  The old
# heuristic picked ``block_b = min(b, 64)`` batch elements regardless of
# ``m_mult`` (rows per element), so an im2col chain with m_mult0 = 32 could
# stage thousands of x/scratch rows per grid step; bounding the ROWS keeps
# the VMEM working set flat across chain geometries (the small-batch ECG
# grid/scratch fix of ISSUE 6).
DEFAULT_ROW_BUDGET = 512
# lane width of the flatten staging buffer: Mosaic's strided row loads
# need a base memref exactly one vreg (128 lanes) wide
_LANES = 128


class MegaLayerMeta(NamedTuple):
    """Static schedule entry for one layer of a packed megakernel chain.

    All fields are Python ints/bools/strs (hashable: the schedule tuple is
    a jit-static argument and pytree metadata).
    """

    row0: int        # first row of this layer's weights in w_cat
    c0: int          # first row of this layer's offsets in off_cat
    k: int           # logical input width (pre chunk padding)
    k_pad: int       # padded input width (w_eff rows)
    n: int           # output width
    n_chunks: int    # k_pad // chunk_rows
    shift: int       # relu_shift right-shift amount (inter-layer layers)
    relu_shift: bool  # True: hand 5-bit codes to the next layer in-kernel
    flatten: int     # cols-merge factor into the next layer (1 = none)
    m_mult: int      # input rows per final batch row at this layer
    # input encoding of THIS layer: "codes" (5-bit codes arrive as-is),
    # "unsigned" (float features quantized at the baked LSB), "split"
    # (signed-split pos/neg passes, subtracted digitally in-kernel)
    encode: str = "codes"
    # hand-off domain to the NEXT layer: "codes" | "relu" | "attn" |
    # "res_ln" | "swiglu" (inter-layer) and "raw" | "res_out" (final)
    handoff: str = ""


class BlockMeta(NamedTuple):
    """Static transformer-block glue geometry (attention+MLP megakernel).

    Hashable jit-static companion of the 4-layer schedule
    ``[qkv, o, up_gate, down]`` with hand-offs
    ``[attn, res_ln, swiglu, res_out]``.
    """

    n_heads: int
    n_kv_heads: int
    head_dim: int
    seq: int
    rope_theta: float
    d_ff: int
    eps: float = 1e-5


def default_block_b(b: int, m_mult0: int,
                    row_budget: int = DEFAULT_ROW_BUDGET) -> int:
    """Batch elements per grid step so that ``block_b * m_mult0`` rows stay
    within the VMEM row budget (never below 1, never above the batch)."""
    return max(1, min(b, max(1, row_budget // max(1, m_mult0))))


def _rmsnorm(h: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the trailing axis - the exact op order of
    :func:`repro.models.layers.norm_apply` (rsqrt of the mean square
    summed in the stated order, then the learned scale), so the in-kernel
    glue is bit-identical to the model path.  Imported lazily, as the
    attention glue below: kernels sit below models."""
    from repro.models.layers import ordered_sum

    y = h * jax.lax.rsqrt(ordered_sum(h * h) / h.shape[-1] + eps)
    return y * scale


def _quantize_codes(h: jax.Array, scale: jax.Array) -> jax.Array:
    """Forward-only 5-bit unsigned quantization (value-identical to
    :func:`repro.core.quant.quantize_act`; the STE lives in the ref)."""
    return jnp.clip(jnp.round(h / scale), 0.0, float(BSS2.a_max))


def _pad_width(a: jax.Array, width: int) -> jax.Array:
    pad = width - a.shape[1]
    if pad:
        a = jnp.concatenate(
            [a, jnp.zeros((a.shape[0], pad), jnp.float32)], axis=1
        )
    return a


def _flatten_positions(stage_ref, h: jax.Array, flatten: int) -> jax.Array:
    """im2col flatten: merge the position rows into the next layer's
    contraction axis, ``[rows, n] -> [rows // flatten, flatten * n]``
    (row ``e * flatten + p`` lands in columns ``p*n:(p+1)*n`` of row
    ``e``).  Mosaic lowers no sublane->lane shape cast, so the relabel
    goes through a 128-lane staging buffer: one strided row load per
    position and lane block, concatenated along lanes.  A pure data
    movement - bit-identical to the row-major ``reshape``."""
    rows, n = h.shape
    pieces = [[] for _ in range(flatten)]
    for c0 in range(0, n, _LANES):
        width = min(_LANES, n - c0)
        stage_ref[0:rows, :] = _pad_width(h[:, c0:c0 + width], _LANES)
        for p in range(flatten):
            blk = stage_ref[pl.ds(p, rows // flatten, stride=flatten), :]
            pieces[p].append(blk[:, :width])
    return jnp.concatenate([b for per_pos in pieces for b in per_pos],
                           axis=1)


def _adc_accumulate(h, w_l, gain, off_rows, meta: MegaLayerMeta, *,
                    chunk_rows: int, faithful: bool):
    """Chunked saturating analog VMM for one scheduled layer (in-kernel):
    per 128-row chunk, MXU dot + gain + fixed-pattern offset, 8-bit ADC
    round/clip (faithful) and digital accumulation - the same arithmetic
    as :func:`repro.kernels.analog_mvm._kernel`, unrolled over the static
    chunk count."""
    acc = jnp.zeros((h.shape[0], w_l.shape[1]), jnp.float32)
    for c in range(meta.n_chunks):
        v = mxu_dot(h[:, c * chunk_rows:(c + 1) * chunk_rows],
                    w_l[c * chunk_rows:(c + 1) * chunk_rows, :])
        v = v * gain + off_rows[c]
        if faithful:
            v = jnp.clip(jnp.round(v), float(BSS2.adc_min),
                         float(BSS2.adc_max))
        acc = acc + v
    if not faithful:
        lo = float(BSS2.adc_min) * meta.n_chunks
        hi = float(BSS2.adc_max) * meta.n_chunks
        acc = jnp.clip(jnp.round(acc), lo, hi)
    return acc


def _layer_handoff(meta: MegaLayerMeta, last: bool) -> str:
    """Resolve a schedule entry's hand-off tag (legacy entries built
    before the domain tags carry ``handoff == ""``)."""
    if meta.handoff:
        return meta.handoff
    if last:
        return "raw"
    return "codes" if meta.relu_shift else "relu"


def _plan_kernel(*refs, schedule: Tuple[MegaLayerMeta, ...],
                 chunk_rows: int, faithful: bool, n_max: int, block_b: int,
                 block: Optional[BlockMeta],
                 has_extras: bool):
    if has_extras:
        (x_ref, w_ref, gain_ref, off_ref,
         deq_ref, bias_ref, enc_ref, *rest) = refs
    else:
        x_ref, w_ref, gain_ref, off_ref, *rest = refs
        deq_ref = bias_ref = enc_ref = None
    if block is not None:
        ln_ref, o_ref, h_ref, res_ref, *stage = rest
    else:
        o_ref, h_ref, *stage = rest
        ln_ref = res_ref = None
    stage_ref = stage[0] if stage else None

    w_all = w_ref[...]
    last = len(schedule) - 1
    xf = x_ref[...].astype(jnp.float32)      # [block_b * m_mult0, k0_pad]

    if block is not None:
        # block entry glue: save the residual stream, RMSNorm(ln1) the
        # float features for the QKV layer's in-kernel encoder
        d0 = schedule[0].k
        ln_all = ln_ref[...]
        res = xf[:, :d0]
        res_ref[0:res.shape[0], 0:d0] = res
        h = _rmsnorm(res, ln_all[0, :d0], block.eps)
    else:
        h = xf

    for li, meta in enumerate(schedule):
        rows = block_b * meta.m_mult
        w_l = w_all[meta.row0:meta.row0 + meta.k_pad, :]
        off_rows = [off_ref[meta.c0 + c, :] for c in range(meta.n_chunks)]
        gain = gain_ref[li, :]
        mm = functools.partial(
            _adc_accumulate, w_l=w_l, gain=gain, off_rows=off_rows,
            meta=meta, chunk_rows=chunk_rows, faithful=faithful,
        )
        if meta.encode == "codes":
            # h already holds (padded) 5-bit codes
            acc = mm(h)
        else:
            # float features: encode at the baked static LSB in-kernel -
            # same quantize-then-pad order as the per-layer executor
            scale = enc_ref[li, 0]
            f = h[:, :meta.k]
            if meta.encode == "split":
                a_pos = _pad_width(_quantize_codes(f, scale), meta.k_pad)
                a_neg = _pad_width(_quantize_codes(-f, scale), meta.k_pad)
                acc = mm(a_pos) - mm(a_neg)
            else:
                acc = mm(_pad_width(_quantize_codes(f, scale), meta.k_pad))

        handoff = _layer_handoff(meta, li == last)
        if li == last:
            if handoff == "res_out":
                # final dequant + bias + residual: the block's float
                # output leaves the kernel fully glued
                y = (acc[:, :meta.n] * deq_ref[li, :meta.n]
                     + bias_ref[li, :meta.n])
                out = res_ref[0:rows, 0:meta.n] + y
                o_ref[...] = _pad_width(out, n_max)
            else:
                # "raw": accumulated ADC codes leave the kernel;
                # dequantization to float happens outside, like the
                # per-layer executor's epilogue == "none" hand-off
                o_ref[...] = acc
            return

        if handoff == "codes":
            # inter-layer ADC epilogue (paper §II-A): ReLU at the readout
            # + right-shift requantization onto the 5-bit code range
            nxt = jnp.maximum(acc, 0.0)
            nxt = jnp.floor(nxt / float(1 << meta.shift))
            nxt = jnp.clip(nxt, 0.0, float(BSS2.a_max))[:, :meta.n]
            if meta.flatten > 1:
                nxt = _flatten_positions(stage_ref, nxt, meta.flatten)
        else:
            # float-domain hand-off: dequantize at the packed per-column
            # rows (a_scale * w_scale / gain) + bias, then run the glue
            y = (acc[:, :meta.n] * deq_ref[li, :meta.n]
                 + bias_ref[li, :meta.n])
            if handoff == "relu":
                nxt = jnp.maximum(y, 0.0)
                if meta.flatten > 1:
                    nxt = _flatten_positions(stage_ref, nxt, meta.flatten)
            elif handoff == "attn":
                # fused QKV -> RoPE + causal softmax attention; the SAME
                # function the model path calls (parity by construction).
                # Imported lazily: kernels are below models in the layer
                # stack, and the body only runs at trace time.
                from repro.models.attention import prefill_attention_glue

                nxt = prefill_attention_glue(
                    y, batch=block_b, seq=block.seq,
                    n_heads=block.n_heads, n_kv_heads=block.n_kv_heads,
                    head_dim=block.head_dim, rope_theta=block.rope_theta,
                )
            elif handoff == "res_ln":
                r = res_ref[0:rows, 0:meta.n] + y
                res_ref[0:rows, 0:meta.n] = r       # x <- x + attn_out
                nxt = _rmsnorm(r, ln_ref[...][1, :meta.n], block.eps)
            elif handoff == "swiglu":
                up = y[:, :block.d_ff]
                gate = y[:, block.d_ff:]
                nxt = jax.nn.silu(gate) * up
            else:
                raise ValueError(f"unknown hand-off {handoff!r}")
        # the inter-layer activations round-trip through VMEM scratch -
        # the software mirror of the on-chip activation memory: they
        # never leave the core between layers
        nxt = _pad_width(nxt, n_max)
        h_ref[0:nxt.shape[0], :] = nxt
        h = h_ref[0:nxt.shape[0], :]


@functools.partial(
    jax.jit,
    static_argnames=(
        "schedule", "chunk_rows", "faithful", "block_b", "interpret",
        "block",
    ),
)
def analog_plan_pallas(
    x_in: jax.Array,                 # [B * m_mult0, k0_pad] codes or floats
    w_cat: jax.Array,                # [sum(k_pad), n_max] packed weights
    gain_all: jax.Array,             # [L, n_max] per-layer gains
    off_cat: jax.Array,              # [sum(n_chunks), n_max] offsets
    deq: Optional[jax.Array] = None,     # [L, n_max] dequant rows
    bias: Optional[jax.Array] = None,    # [L, n_max] biases (0 where none)
    enc: Optional[jax.Array] = None,     # [L, 1] input-encoding LSBs
    ln: Optional[jax.Array] = None,      # [2, n_max] block ln1/ln2 scales
    *,
    schedule: Tuple[MegaLayerMeta, ...],
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
    block_b: int = 8,
    interpret: bool = False,
    block: Optional[BlockMeta] = None,
) -> jax.Array:
    """Execute a packed AnalogPlan chain in ONE kernel launch.

    ``x_in`` holds 5-bit codes when ``schedule[0].encode == "codes"``,
    else float features encoded in-kernel at ``enc[0]``.  Returns the
    final layer's raw accumulated ADC codes ``[B * m_mult_last, n_last]``
    (handoff "raw"; the caller dequantizes exactly like the per-layer
    executor) or the fully-glued float block output (handoff "res_out").
    Bit-exact against the layer-by-layer replay (tested).
    """
    assert len(schedule) >= 1
    has_extras = deq is not None
    needs_extras = any(m.encode != "codes" for m in schedule) or any(
        _layer_handoff(m, i == len(schedule) - 1) not in ("codes", "raw")
        for i, m in enumerate(schedule)
    )
    assert has_extras or not needs_extras, (
        "float-domain schedule entries need the packed deq/bias/enc "
        "operands (repro.exec.lower.pack_megakernel builds them)"
    )
    assert block is None or ln is not None
    m0, m_last = schedule[0].m_mult, schedule[-1].m_mult
    n_max = w_cat.shape[1]
    assert x_in.shape[0] % m0 == 0, (x_in.shape, m0)
    b = x_in.shape[0] // m0

    pb = (-b) % block_b
    if pb:
        # zero pad rows form whole fake batch elements that stay in their
        # own rows end to end (the chain only contracts over K; the block
        # glue's softmax stays finite on all-zero rows) and are sliced off
        # below
        x_in = jnp.pad(x_in, ((0, pb * m0), (0, 0)))
    b_pad = b + pb

    scratch_rows = block_b * max(
        (m.m_mult for m in schedule[1:]), default=1
    )
    operands = [x_in.astype(jnp.float32), w_cat.astype(jnp.float32),
                gain_all, off_cat]
    in_specs = [
        pl.BlockSpec((block_b * m0, x_in.shape[1]), lambda i: (i, 0)),
        # constant index maps: packed operands stay VMEM-resident
        # across batch blocks instead of re-streaming per layer
        pl.BlockSpec(w_cat.shape, lambda i: (0, 0)),
        pl.BlockSpec(gain_all.shape, lambda i: (0, 0)),
        pl.BlockSpec(off_cat.shape, lambda i: (0, 0)),
    ]
    if has_extras:
        for arr in (deq, bias, enc):
            operands.append(jnp.asarray(arr, jnp.float32))
            in_specs.append(pl.BlockSpec(arr.shape, lambda i: (0, 0)))
    scratch_shapes = [
        # inter-layer activations (codes or floats) live HERE
        pltpu.VMEM((scratch_rows, n_max), jnp.float32)
    ]
    if block is not None:
        operands.append(jnp.asarray(ln, jnp.float32))
        in_specs.append(pl.BlockSpec(ln.shape, lambda i: (0, 0)))
        # the fp32 residual stream of the transformer block
        scratch_shapes.append(
            pltpu.VMEM((block_b * m0, n_max), jnp.float32)
        )
    flat_rows = [m.m_mult for m in schedule if m.flatten > 1]
    if flat_rows:
        # the flatten layers' position rows, staged for the relabel
        scratch_shapes.append(
            pltpu.VMEM((block_b * max(flat_rows), _LANES), jnp.float32)
        )
    grid = (b_pad // block_b,)
    out = pl.pallas_call(
        functools.partial(
            _plan_kernel, schedule=schedule, chunk_rows=chunk_rows,
            faithful=faithful, n_max=n_max, block_b=block_b,
            block=block,
            has_extras=has_extras,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b * m_last, n_max), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad * m_last, n_max), jnp.float32),
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(*operands)
    return out[: b * m_last, : schedule[-1].n]
