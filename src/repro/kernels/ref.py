"""Pure-jnp oracles for the Pallas kernels, forward-only reference
semantics.  They are also the one jnp implementation of the analog VMM:
:mod:`repro.kernels.ops` runs them wherever it does not run a kernel, and
the interpreted kernels are tested against them bit for bit."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.hw import BSS2
from repro.core.quant import ANALOG_PRECISION
from repro.kernels.analog_mvm import code_dot, split_bf16x3


def analog_mvm_ref(
    a_code: jax.Array,          # [M, K] integer-valued float, 0..31
    w_eff: jax.Array,           # [K, N] effective analog weights
    gain: jax.Array,            # [N] or scalar
    chunk_offset: Optional[jax.Array],  # [C, N] or None
    *,
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
) -> jax.Array:
    """Chunked saturating analog VMM oracle.  K must divide into chunks.

    A scan over the chunks whose body is one chunk's whole chain, as the
    kernel's grid step has it: the CPU backend may fuse ``v * gain + off``
    into one rounding or not depending on what surrounds it, and the same
    body keeps the oracle and the interpreted kernel on one side of that
    choice.  The live set stays ``[M, N]``."""
    m, k = a_code.shape
    n = w_eff.shape[1]
    assert k % chunk_rows == 0, (k, chunk_rows)
    c = k // chunk_rows
    a_c = jnp.moveaxis(
        a_code.reshape(m, c, chunk_rows).astype(jnp.float32), 1, 0)
    w_c = w_eff.reshape(c, chunk_rows, n).astype(jnp.float32)
    off = (jnp.zeros((c, 1), jnp.float32) if chunk_offset is None
           else jnp.asarray(chunk_offset, jnp.float32))

    def step(acc, xs):
        a_i, w_i, o_i = xs
        v = code_dot(a_i, split_bf16x3(w_i)) * gain + o_i
        if faithful:
            v = jnp.clip(jnp.round(v), BSS2.adc_min, BSS2.adc_max)
        return acc + v, None

    acc, _ = jax.lax.scan(step, jnp.zeros((m, n), jnp.float32),
                          (a_c, w_c, off))
    if faithful:
        return acc
    return jnp.clip(jnp.round(acc), BSS2.adc_min * c, BSS2.adc_max * c)


def analog_mvm_split_ref(
    a_pos: jax.Array,
    a_neg: jax.Array,
    w_eff: jax.Array,
    gain: jax.Array,
    chunk_offset: Optional[jax.Array],
    *,
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
) -> jax.Array:
    """Two-pass signed-split oracle: positive and negative activation parts
    as two independent analog runs on the same tiles, subtracted digitally.
    This is the semantics the fused kernel must reproduce bit-exactly."""
    yp = analog_mvm_ref(a_pos, w_eff, gain, chunk_offset,
                        chunk_rows=chunk_rows, faithful=faithful)
    yn = analog_mvm_ref(a_neg, w_eff, gain, chunk_offset,
                        chunk_rows=chunk_rows, faithful=faithful)
    return yp - yn


def analog_plan_ref(
    x_in: jax.Array,             # [B * m_mult0, k0_pad] codes or floats
    w_cat: jax.Array,            # [sum(k_pad), n_max] packed weights
    gain_all: jax.Array,         # [L, n_max] per-layer gains
    off_cat: jax.Array,          # [sum(n_chunks), n_max] offsets
    schedule,                    # tuple of MegaLayerMeta (duck-typed)
    *,
    chunk_rows: int = BSS2.signed_rows,
    faithful: bool = True,
    extras=None,                 # (deq [L,n_max], bias [L,n_max],
                                 #  enc [L,1], ln [2,n_max] | None)
    block=None,                  # BlockMeta | None (transformer glue)
) -> jax.Array:
    """Pure-jnp megakernel oracle: a whole packed layer chain (code-domain
    hand-offs, float-domain hand-offs, or the fused attention+MLP block)
    as one traced function - the CPU hot path of the plan megakernel and
    the bit-exactness reference for the Pallas kernel.

    Gradient contract (HIL, paper §III-B): the saturating ADC is applied
    as a pure straight-through term (``v + sg(adc(v) - v)``), gain and
    offsets are frozen via ``stop_gradient`` - exactly the linearized
    backward of :func:`repro.kernels.ops.analog_mvm`.  Float-domain glue follows
    the per-layer executor's gradient semantics: in-kernel encoding uses
    the STE quantizer (:func:`repro.core.quant.quantize_act`), the
    ``"relu"`` hand-off uses ``jax.nn.relu`` (zero gradient at exactly-0
    accumulators, matching ``run``'s float glue), and gradients flow into
    the packed dequant/bias/norm leaves just as they do through the
    per-layer dequantization.  Differentiating this oracle therefore
    reproduces the per-layer STE/HIL gradients while the forward stays
    bit-identical (same per-chunk dot shapes and op order).
    """
    from repro.core.quant import quantize_act
    from repro.kernels.analog_plan import _layer_handoff, _rmsnorm

    sg = jax.lax.stop_gradient
    deq = bias = enc = ln = None
    if extras is not None:
        deq, bias, enc, ln = extras
    h = x_in.astype(jnp.float32)
    res = None
    last = len(schedule) - 1
    if block is not None:
        d0 = schedule[0].k
        res = h[:, :d0]
        h = _rmsnorm(res, ln[0, :d0], block.eps)

    for li, meta in enumerate(schedule):
        w_l = w_cat[meta.row0:meta.row0 + meta.k_pad, :meta.n]
        gain = sg(gain_all[li, :meta.n])
        offs = [sg(off_cat[meta.c0 + c, :meta.n])
                for c in range(meta.n_chunks)]

        def mvm(a, w_l=w_l, gain=gain, offs=offs, meta=meta):
            acc = jnp.zeros((a.shape[0], meta.n), jnp.float32)
            for c in range(meta.n_chunks):
                a_c = a[:, c * chunk_rows:(c + 1) * chunk_rows]
                w_c = w_l[c * chunk_rows:(c + 1) * chunk_rows, :]
                v = jnp.einsum("...k,kn->...n", a_c, w_c,
                               precision=ANALOG_PRECISION,
                               preferred_element_type=jnp.float32)
                v = v * gain + offs[c]
                if faithful:
                    adc = jnp.clip(jnp.round(v), BSS2.adc_min, BSS2.adc_max)
                    v = v + sg(adc - v)
                acc = acc + v
            if not faithful:
                lo = float(BSS2.adc_min) * meta.n_chunks
                hi = float(BSS2.adc_max) * meta.n_chunks
                acc = acc + sg(jnp.clip(jnp.round(acc), lo, hi) - acc)
            return acc

        if meta.encode == "codes":
            acc = mvm(h)
        else:
            # float features: STE-encode at the baked static LSB, then
            # pad codes to the chunk width (quantize-then-pad, the same
            # order as the kernel and the per-layer executor)
            scale = enc[li, 0]
            f = h[:, :meta.k]
            pad = meta.k_pad - meta.k

            def padc(a, pad=pad):
                return jnp.pad(a, ((0, 0), (0, pad))) if pad else a

            if meta.encode == "split":
                acc = mvm(padc(quantize_act(f, scale))) - mvm(
                    padc(quantize_act(-f, scale)))
            else:
                acc = mvm(padc(quantize_act(f, scale)))

        handoff = _layer_handoff(meta, li == last)
        if li == last:
            if handoff == "res_out":
                y = acc * deq[li, :meta.n] + bias[li, :meta.n]
                return res + y
            return acc

        if handoff == "codes":
            # inter-layer ADC epilogue, STE grads (== run._epilogue_ste)
            codes = jnp.maximum(acc, 0.0)
            shifted = codes / float(1 << meta.shift)
            codes = shifted + sg(jnp.floor(shifted) - shifted)
            nxt_h = jnp.clip(codes, 0.0, float(BSS2.a_max))
            if meta.flatten > 1:
                nxt_h = nxt_h.reshape(nxt_h.shape[0] // meta.flatten,
                                      meta.flatten * meta.n)
        else:
            y = acc * deq[li, :meta.n] + bias[li, :meta.n]
            if handoff == "relu":
                nxt_h = jax.nn.relu(y)
                if meta.flatten > 1:
                    nxt_h = nxt_h.reshape(nxt_h.shape[0] // meta.flatten,
                                          meta.flatten * meta.n)
            elif handoff == "attn":
                from repro.models.attention import prefill_attention_glue

                batch = y.shape[0] // block.seq
                nxt_h = prefill_attention_glue(
                    y, batch=batch, seq=block.seq,
                    n_heads=block.n_heads, n_kv_heads=block.n_kv_heads,
                    head_dim=block.head_dim, rope_theta=block.rope_theta,
                )
            elif handoff == "res_ln":
                res = res + y
                nxt_h = _rmsnorm(res, ln[1, :meta.n], block.eps)
            elif handoff == "swiglu":
                up = y[:, :block.d_ff]
                gate = y[:, block.d_ff:]
                nxt_h = jax.nn.silu(gate) * up
            else:
                raise ValueError(f"unknown hand-off {handoff!r}")

        nxt = schedule[li + 1]
        if nxt.encode == "codes":
            pad = nxt.k_pad - nxt_h.shape[1]
            if pad:
                nxt_h = jnp.pad(nxt_h, ((0, 0), (0, pad)))
        h = nxt_h
    return acc


def maxmin_pool_ref(x: jax.Array, window: int = 32) -> jax.Array:
    """FPGA preprocessing pooling (paper Fig. 7): per non-overlapping window,
    max - min.  x: [..., T] with T % window == 0 -> [..., T // window]."""
    t = x.shape[-1]
    assert t % window == 0, (t, window)
    xw = x.reshape(x.shape[:-1] + (t // window, window))
    return xw.max(axis=-1) - xw.min(axis=-1)
