"""Plan execution: ``run(plan, x)`` replays a pre-lowered analog program.

Responsibilities left at run time (everything else was baked by
:mod:`repro.exec.lower`):

- dynamic activation calibration (per-call abs-max, the FPGA right-shift
  choice) when ``cfg.act_calib == "dynamic"``,
- signed-input encoding of the incoming activations (split/offset/none),
- dispatch of the analog passes - ONE signed-split dispatch per split
  layer (:func:`repro.kernels.ops.analog_mvm_split`); only noisy passes,
  which need independent readout keys, run as two,
- the inter-layer ADC epilogue: ReLU + right-shift requantization to
  5-bit codes (paper §II-A), as elementwise STE ops,
- temporal readout noise keys (mock-mode training),
- megakernel routing: :func:`run` replays an eligible plan (packed at
  lower time, see ``exec.lower.pack_megakernel``) as ONE dispatch - the
  whole chain in a single ``pallas_call`` with VMEM-resident inter-layer
  activations, or as one fused jnp chain where the kernels run their
  oracle.  Code-domain chains, static-calib float/mixed chains and fused
  attention+MLP block plans (``plan.block``) all take this route; noisy
  replay, dynamic-calib float hand-offs, stacked plans and inputs the
  packed schedule cannot take replay layer by layer (:func:`run_layers`;
  :func:`megakernel_fallback_reason` says why).

Which implementation computes each analog pass - a Pallas kernel or its
jnp oracle - is :mod:`repro.kernels.ops`' choice, from ``cfg.use_pallas``.

Dispatch accounting: every analog pass issued by the executor bumps
:data:`ANALOG_DISPATCHES` at trace time - tests and benchmarks use
:func:`reset_dispatch_count` / :func:`dispatch_count` to check a replay
against :attr:`repro.exec.plan.AnalogPlan.expected_dispatches`.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.analog import AnalogConfig, analog_matmul
from repro.core.hw import BSS2
from repro.exec.plan import (
    EPILOGUE_NONE,
    EPILOGUE_RELU_SHIFT,
    GROUP_BATCH_CONCAT,
    GROUP_COLUMN_CONCAT,
    GROUP_EXPERT_STACK,
    AnalogPlan,
    GroupPlan,
    LayerPlan,
)
from repro.obs import metrics as _obs_metrics

ANALOG_DISPATCHES = 0

def reset_dispatch_count() -> None:
    global ANALOG_DISPATCHES
    ANALOG_DISPATCHES = 0


def dispatch_count() -> int:
    return ANALOG_DISPATCHES


def _count(n: int = 1) -> None:
    # Host-side, trace-time only (like ANALOG_DISPATCHES itself): a
    # cached-jit replay bumps neither the module counter nor the metric.
    global ANALOG_DISPATCHES
    ANALOG_DISPATCHES += n
    _obs_metrics.counter("exec.dispatches").inc(n)


def _pad_codes(a: jax.Array, k_pad: int) -> jax.Array:
    pad = k_pad - a.shape[-1]
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a


def _epilogue_ste(y_int: jax.Array, shift: int) -> jax.Array:
    """Elementwise ADC epilogue with straight-through gradients: ReLU at
    the (offset-aligned) readout, then right-shift requantization onto the
    5-bit activation range.  Value-identical to the megakernel's code
    hand-off."""
    return quant.requantize_5bit(jnp.maximum(y_int, 0.0), shift)


def run_layer(
    lp: LayerPlan,
    x: jax.Array,
    cfg: AnalogConfig,
    *,
    key: Optional[jax.Array] = None,
    x_is_codes: bool = False,
) -> jax.Array:
    """Execute one lowered layer: x [..., K] -> y [..., N].

    ``x_is_codes=True`` means ``x`` already holds unsigned 5-bit event
    codes (LSB 1.0) - the hand-off format of a preceding ``relu_shift``
    epilogue or the preprocessed ECG input - so quantization is skipped.
    Output: float activations when ``lp.epilogue == "none"`` (dequantized,
    bias applied), else 5-bit codes for the next stacked layer.
    """
    in_dtype = x.dtype
    x = x.astype(jnp.float32)
    k_pad = lp.k_pad
    rk = None if (cfg.deterministic or key is None) else key

    if x_is_codes:
        a_scale = jnp.asarray(1.0, jnp.float32)
    elif cfg.act_calib == "dynamic":
        # per-call abs-max calibration (the FPGA preprocessing / SIMD-CPU
        # right-shift choice on hardware)
        a_scale = quant.act_scale_from_max(
            jax.lax.stop_gradient(jnp.abs(x)).max() + 1e-9
        )
    else:
        # static calibration: a layer that belongs to a snapshot-
        # calibrated fused group encodes at the group's SHARED input LSB
        # (a_scale_in, the widest member scale) instead of its own
        # calibrated a_scale; dequantization below always uses the LSB
        # the codes were actually encoded at.
        a_scale = lp.a_scale_in if lp.a_scale_in is not None else lp.a_scale
    gain = lp.gain

    signed = "none" if x_is_codes else lp.signed_input
    if signed == "none":
        a_code = x if x_is_codes else quant.quantize_act(x, a_scale)
        a_code = _pad_codes(a_code, k_pad)
        _count()
        y_int = analog_matmul(a_code, lp.w_eff, gain, lp.chunk_offset, rk,
                              cfg)
    elif signed == "split":
        a_pos = _pad_codes(quant.quantize_act(x, a_scale), k_pad)
        a_neg = _pad_codes(quant.quantize_act(-x, a_scale), k_pad)
        if rk is None:
            # ONE dispatch over shared weight tiles for both passes
            from repro.kernels import ops as kernel_ops

            batch_shape = a_pos.shape[:-1]
            _count()
            y2 = kernel_ops.analog_mvm_split(
                a_pos.reshape(-1, k_pad), a_neg.reshape(-1, k_pad),
                lp.w_eff, jnp.broadcast_to(gain, (lp.n,)), lp.chunk_offset,
                lp.chunk_rows, cfg.mode != "analog_fast", cfg.use_pallas,
            )
            y_int = y2.reshape(batch_shape + (lp.n,))
        else:
            # noisy passes need independent readout keys: two dispatches
            k1, k2 = (None, None) if rk is None else tuple(
                jax.random.split(rk)
            )
            _count(2)
            y_int = analog_matmul(a_pos, lp.w_eff, gain, lp.chunk_offset,
                                  k1, cfg) - \
                analog_matmul(a_neg, lp.w_eff, gain, lp.chunk_offset, k2,
                              cfg)
    elif signed == "offset":
        # single pass with offset-encoded activations and a digital
        # correction  y = (a + h) @ W - h * colsum(W); gain derated for the
        # common-mode ADC headroom (cf. Weis et al.).
        half = (BSS2.a_max + 1) // 2
        a_scale = a_scale * 2.0
        rms = cfg.act_rms_codes
        gain = gain * rms / jnp.sqrt(rms**2 + float(half) ** 2)
        a_code = jnp.clip(
            quant._round_ste(x / a_scale) + half, 0.0, float(BSS2.a_max)
        )
        a_code = _pad_codes(a_code, k_pad)
        _count()
        y_int = analog_matmul(a_code, lp.w_eff, gain, lp.chunk_offset, rk,
                              cfg)
        y_int = y_int - gain * half * lp.colsum
    else:
        raise ValueError(f"unknown signed_input {signed!r}")

    if lp.epilogue == EPILOGUE_RELU_SHIFT:
        # inter-layer ADC epilogue: output is 5-bit codes, not floats
        return _epilogue_ste(y_int, lp.shift)
    y = y_int * (a_scale * lp.w_scale.reshape(-1) / gain)
    if lp.bias is not None:
        y = y + lp.bias
    return y.astype(in_dtype)


def run_batch_concat(
    gp: GroupPlan,
    xs,
    cfg: AnalogConfig,
    *,
    key: Optional[jax.Array] = None,
):
    """Replay a ``batch_concat`` fusion group: G same-geometry layers
    with DIFFERENT inputs execute as ONE analog dispatch (the RWKV
    r/k/v/g fusion, 4 -> 1).

    ``xs`` is the ordered sequence of member inputs (same shape each,
    ``gp.member_names`` order); returns the tuple of member outputs.

    On hardware the member matrices occupy disjoint column blocks of one
    array configuration and the stacked input batches stream through in
    a single pass; the emulator computes exactly the member-diagonal
    results of that pass as a vmapped member-axis dispatch (the
    discarded off-diagonal columns cannot affect the kept ones - ADC
    column independence).  Each member's rows encode at that member's
    own activation scale - the per-vector FPGA preprocessing - so the
    replay is bit-exact vs the G solo dispatches under dynamic AND
    static calibration (vmapping :func:`run_layer` over the member axis
    reproduces the solo arithmetic verbatim, per-member abs-max
    included).
    """
    g = len(gp.member_names)
    if len(xs) != g:
        raise ValueError(
            f"group has {g} members ({gp.member_names}), got {len(xs)} "
            "inputs"
        )
    lp = gp.fused
    if getattr(lp.store.codes, "ndim", 3) != 3:
        raise ValueError(
            "run_batch_concat expects member-leading [G, K_pad, N] plan "
            "leaves (scan-stacked group plans must be sliced by the scan "
            f"first), got codes ndim {lp.store.codes.ndim}"
        )
    x = jnp.stack([jnp.asarray(xi) for xi in xs], axis=0)
    # ONE dispatch for the whole group: the vmapped member axis is a
    # single traced analog pass (run_layer's own counter bumps once)
    if key is None:
        y = jax.vmap(lambda l, xi: run_layer(l, xi, cfg))(lp, x)
    else:
        ks = jax.random.split(key, g)
        y = jax.vmap(
            lambda l, xi, ki: run_layer(l, xi, cfg, key=ki)
        )(lp, x, ks)
    return tuple(y[i] for i in range(g))


def run_expert_stack(
    gp: GroupPlan,
    xe: jax.Array,
    cfg: AnalogConfig,
    *,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Replay an ``expert_stack`` fusion group: ``xe`` [E, C, K] through
    the pre-lowered per-expert plan -> [E, C, N].

    Value-identical to the per-call MoE path
    (:func:`repro.models.moe._analog_expert_matmul`) with the lowering
    hoisted out of the traced forward: one shared dynamic activation
    scale over the whole dispatch buffer, signed inputs via the pos/neg
    split, per-expert column scales and gains baked at compile time.
    ``key`` is accepted for signature uniformity; expert readout noise is
    omitted exactly as on the per-call path (documented in
    :mod:`repro.models.moe`).
    """
    del key
    from repro.core.analog import analog_matmul as _matmul

    lp = gp.fused
    in_dtype = xe.dtype
    xf = xe.astype(jnp.float32)
    a_scale = quant.act_scale_from_max(
        jax.lax.stop_gradient(jnp.abs(xf)).max() + 1e-9
    )
    inner = cfg.replace(use_pallas=False, signed_input="none")
    k_pad = lp.k_pad
    a_pos = _pad_codes(quant.quantize_act(xf, a_scale), k_pad)
    a_neg = _pad_codes(quant.quantize_act(-xf, a_scale), k_pad)

    def one(a, w, g):
        return _matmul(a, w, g, None, None, inner)

    _count()
    gain = lp.gain if lp.gain.ndim == 1 else lp.gain[..., 0]   # [E]
    y_int = jax.vmap(one)(a_pos, lp.w_eff, gain) - jax.vmap(one)(
        a_neg, lp.w_eff, gain
    )
    y = y_int * (a_scale * lp.w_scale / gain[:, None, None])
    return y.astype(in_dtype)


class ExpertRows(NamedTuple):
    """Routed rows sorted into per-expert groups of whole row tiles (the
    layout :func:`repro.models.moe.held_rows` builds): tile ``i``
    (``block_m`` rows) is expert ``tile_expert[i]``'s, the first
    ``live_tiles[0]`` tiles hold every routed row, and row ``r`` carries
    a routed token where ``row_live[r]``."""

    row_live: jax.Array          # [R] bool
    tile_expert: jax.Array       # [R // block_m] int32
    live_tiles: jax.Array        # [1] int32
    block_m: int

    @property
    def row_expert(self) -> jax.Array:
        return jnp.repeat(self.tile_expert, self.block_m)


def run_expert_rows(
    lp: LayerPlan,
    x: jax.Array,
    rows: ExpertRows,
    cfg: AnalogConfig,
) -> jax.Array:
    """Replay an expert-stacked layer plan (leaves ``[E, ...]``, one
    analog layer per held expert, each with its own fixed pattern, gain
    and static input LSB) over expert-sorted rows ``x [R, K]`` as ONE
    grouped dispatch: each row is encoded, run and dequantized as its
    own expert's :func:`run_layer` would (signed-split input).  Rows that
    carry no routed token come back as zeros.  Under dynamic calibration
    the LSB is the abs-max over every routed row of the call."""
    if lp.signed_input != "split":
        raise ValueError(
            f"expert rows run signed-split; the plan is {lp.signed_input!r}"
        )
    e = rows.row_expert
    n_exp = lp.store.codes.shape[0]
    x = jnp.where(rows.row_live[:, None], x.astype(jnp.float32), 0.0)
    if cfg.act_calib == "dynamic":
        a_scale = jnp.broadcast_to(quant.act_scale_from_max(
            jax.lax.stop_gradient(jnp.abs(x)).max() + 1e-9
        ), e.shape)
    else:
        a_scale = lp.a_scale[e]
    a_scale = a_scale[:, None]
    a_pos = _pad_codes(quant.quantize_act(x, a_scale), lp.k_pad)
    a_neg = _pad_codes(quant.quantize_act(-x, a_scale), lp.k_pad)
    gain = jnp.broadcast_to(
        lp.gain.reshape(n_exp, -1), (n_exp, lp.n)).astype(jnp.float32)
    off = lp.chunk_offset
    if off is None:
        off = jnp.zeros((n_exp, lp.k_pad // lp.chunk_rows, lp.n),
                        jnp.float32)
    from repro.kernels import ops as kernel_ops

    _count()
    y_int = kernel_ops.expert_mvm(
        a_pos, a_neg, lp.w_eff, gain, off, rows.tile_expert,
        rows.live_tiles, block_m=rows.block_m, chunk_rows=lp.chunk_rows,
        faithful=cfg.mode != "analog_fast", use_pallas=cfg.use_pallas,
    )
    w_scale = lp.w_scale.reshape(n_exp, lp.n)
    y = y_int * (a_scale * w_scale[e] / gain[e])
    return jnp.where(rows.row_live[:, None], y, 0.0)


def run_group(
    gp: GroupPlan,
    x,
    cfg: AnalogConfig,
    *,
    key: Optional[jax.Array] = None,
):
    """Replay any lowered fusion group.

    - ``column_concat``: ``x`` is the members' SHARED input; returns the
      tuple of member outputs (one fused dispatch, columns split back).
    - ``batch_concat``: ``x`` is the sequence of member inputs; returns
      the tuple of member outputs.
    - ``expert_stack``: ``x`` is the ``[E, C, K]`` dispatch buffer;
      returns the ``[E, C, N]`` expert outputs.
    """
    if gp.kind == GROUP_COLUMN_CONCAT:
        y = run_layer(gp.fused, x, cfg, key=key)
        offs = []
        acc = 0
        for n in gp.member_ns[:-1]:
            acc += n
            offs.append(acc)
        return tuple(jnp.split(y, offs, axis=-1))
    if gp.kind == GROUP_BATCH_CONCAT:
        return run_batch_concat(gp, x, cfg, key=key)
    if gp.kind == GROUP_EXPERT_STACK:
        return run_expert_stack(gp, x, cfg, key=key)
    raise ValueError(f"unknown group kind {gp.kind!r}")


def _megakernel_batch_shape(plan: AnalogPlan, x: jax.Array):
    """Resolve the megakernel's output batch shape from ``x``'s leading
    dims, or return a reason string when the shapes cannot feed the packed
    schedule.  EVERY flatten_out layer consumes the then-trailing batch
    dim (even a size-1 position axis: the per-layer replay merges it into
    the feature axis, so the megakernel's output shape must too)."""
    lead = list(x.shape[:-1])
    for lp, meta in zip(plan.layers[:-1], plan.mega.schedule[:-1]):
        if not lp.flatten_out:
            continue
        if not lead or lead[-1] != meta.flatten:
            return (
                f"flatten layer expects a trailing batch dim of "
                f"{meta.flatten} positions, got input shape {x.shape}"
            )
        lead.pop()
    return tuple(lead)


def _run_megakernel(
    plan: AnalogPlan, x: jax.Array, lead: tuple
) -> jax.Array:
    """Replay a packed plan as ONE analog dispatch: the whole chain inside
    a single ``pallas_call`` (or one fused jnp chain on the non-Pallas
    path), inter-layer activations - 5-bit codes or re-encoded float
    features - VMEM-resident.  Bit-exact vs the layer-by-layer replay
    (same per-chunk ADC arithmetic, same floor-shift epilogue, same
    static encoding LSB and dequantization expression - tested)."""
    from repro.kernels import ops as kernel_ops

    cfg, mega = plan.cfg, plan.mega
    lp = plan.layers[-1]
    x2 = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    if mega.schedule[0].encode == "codes":
        x2 = _pad_codes(x2, plan.layers[0].k_pad)
    _count()
    y_int = kernel_ops.analog_plan_codes(
        x2, mega.w_cat, mega.gain, mega.off,
        schedule=mega.schedule, chunk_rows=mega.chunk_rows,
        faithful=cfg.mode != "analog_fast", use_pallas=cfg.use_pallas,
        extras=mega.extras,
    )
    y_int = y_int.reshape(lead + (lp.n,))
    # identical dequantization to run_layer's epilogue == "none" hand-off:
    # the LSB the last layer's input was actually encoded at (1.0 for raw
    # codes; the baked static scale when the kernel re-encoded floats)
    if mega.schedule[-1].encode == "codes":
        a_scale = jnp.asarray(1.0, jnp.float32)
    else:
        a_scale = lp.a_scale_in if lp.a_scale_in is not None else lp.a_scale
    y = y_int * (a_scale * lp.w_scale.reshape(-1) / lp.gain)
    if lp.bias is not None:
        y = y + lp.bias
    if lp.flatten_out:
        y = y.reshape(y.shape[:-2] + (-1,))
    return y


def _megakernel_route(
    plan: AnalogPlan,
    x: jax.Array,
    cfg: AnalogConfig,
    key: Optional[jax.Array],
    x_is_codes: bool,
):
    """Resolve the megakernel route for one ``run`` call: the output
    batch-shape tuple when it can be taken, else a reason string.
    Structural ineligibility is decided at lower time (no ``mega``
    packing baked); noisy replay, entry-domain mismatches and batch-shape
    mismatches keep the layer-by-layer path."""
    if plan.mega is None:
        from repro.exec.lower import megakernel_ineligible_reason

        return megakernel_ineligible_reason(plan) or "plan was not packed"
    entry = plan.mega.schedule[0].encode
    if entry == "codes" and not x_is_codes:
        return (
            "input is float but the packed chain consumes 5-bit codes "
            "(layer 0 encode 'codes')"
        )
    if entry != "codes" and x_is_codes:
        return (
            "input is codes but the packed chain encodes float "
            f"activations in-kernel (layer 0 encode {entry!r})"
        )
    if key is not None and not cfg.deterministic:
        return "noisy replay (readout-noise keys) is layer-by-layer"
    return _megakernel_batch_shape(plan, x)


def megakernel_fallback_reason(
    plan: AnalogPlan,
    x: jax.Array,
    cfg: AnalogConfig,
    key: Optional[jax.Array],
    x_is_codes: bool,
) -> Optional[str]:
    """Why a ``run`` call cannot take the megakernel route (None = it
    can)."""
    route = _megakernel_route(plan, x, cfg, key, x_is_codes)
    return route if isinstance(route, str) else None


def _run_block_fallback(
    plan: AnalogPlan, x: jax.Array, key: Optional[jax.Array]
) -> jax.Array:
    """Per-layer replay of a fused attention+MLP block plan: 4 analog
    dispatches (fused QKV, o, fused up|gate, down) with the digital glue
    in jnp - the SAME glue functions the megakernel traces, so the two
    routes are bit-exact against each other (tested)."""
    from repro.models.attention import prefill_attention_glue
    from repro.models.layers import norm_apply

    bg, cfg = plan.block, plan.cfg
    qkv_lp, o_lp, ug_lp, dn_lp = plan.layers
    b, s, _ = x.shape
    ks = list(jax.random.split(key, 4)) if key is not None else [None] * 4
    res = x.astype(jnp.float32)
    h = norm_apply({"scale": bg.ln1}, res, eps=bg.eps)
    qkv = run_layer(qkv_lp, h, cfg, key=ks[0])
    nq = bg.n_heads * bg.head_dim
    o_in = prefill_attention_glue(
        qkv.reshape(b * s, qkv_lp.n), batch=b, seq=s,
        n_heads=bg.n_heads, n_kv_heads=bg.n_kv_heads,
        head_dim=bg.head_dim, rope_theta=bg.rope_theta,
    )
    attn_out = run_layer(o_lp, o_in.reshape(b, s, nq), cfg, key=ks[1])
    res = res + attn_out
    h = norm_apply({"scale": bg.ln2}, res, eps=bg.eps)
    ug = run_layer(ug_lp, h, cfg, key=ks[2])
    up, gate = ug[..., :bg.d_ff], ug[..., bg.d_ff:]
    y = run_layer(dn_lp, jax.nn.silu(gate) * up, cfg, key=ks[3])
    return (res + y).astype(x.dtype)


def _run_block(
    plan: AnalogPlan,
    x: jax.Array,
    key: Optional[jax.Array],
) -> jax.Array:
    """Execute a block plan (:func:`repro.exec.lower.lower_block`):
    ``x [batch, seq, d_model]`` -> same shape, the whole attention+MLP
    block as ONE analog dispatch (5 on the unlowered model path, 4 on the
    per-layer replay, which noisy calls take)."""
    from repro.kernels import ops as kernel_ops

    bg, cfg, mega = plan.block, plan.cfg, plan.mega
    if x.ndim != 3 or x.shape[-1] != plan.layers[0].k:
        raise ValueError(
            f"block plan expects [batch, seq, {plan.layers[0].k}] float "
            f"activations, got shape {x.shape}"
        )
    if x.shape[1] != bg.seq:
        raise ValueError(
            f"block plan was lowered for the static prefill length "
            f"seq={bg.seq}, got seq={x.shape[1]}; re-lower for this "
            "length (the in-kernel attention bakes its positions)"
        )
    if key is not None and not cfg.deterministic:
        return run_layers(plan, x, key=key)
    _obs_metrics.counter("exec.run.megakernel").inc()
    b, s, d = x.shape
    _count()
    y = kernel_ops.analog_plan_codes(
        x.astype(jnp.float32).reshape(b * s, d),
        mega.w_cat, mega.gain, mega.off,
        schedule=mega.schedule, chunk_rows=mega.chunk_rows,
        faithful=cfg.mode != "analog_fast", use_pallas=cfg.use_pallas,
        extras=mega.extras, block=mega.block,
    )
    return y.reshape(b, s, d).astype(x.dtype)


def run(
    plan: AnalogPlan,
    x: jax.Array,
    *,
    key: Optional[jax.Array] = None,
    x_is_codes: Optional[bool] = None,
) -> jax.Array:
    """Execute a whole lowered stack: one jitted analog program.

    Layers whose predecessor emitted a ``relu_shift`` epilogue consume
    5-bit codes directly (no dequant/requant glue); ``x_is_codes`` states
    whether the initial input already is codes (default: the plan's baked
    ``input_domain``; plans built without one fall back to the legacy
    first-layer-epilogue inference).

    The call takes the whole-plan single-dispatch megakernel whenever the
    plan and the call allow it (code-domain, static-calib float/mixed
    chains and fused attention+MLP blocks), else the per-layer replay of
    :func:`run_layers`; :func:`megakernel_fallback_reason` names why.
    """
    if plan.block is not None:
        return _run_block(plan, x, key)
    if x_is_codes is None:
        x_is_codes = plan.expects_codes
    route = _megakernel_route(plan, x, plan.cfg, key, x_is_codes)
    if isinstance(route, str):
        return run_layers(plan, x, key=key, x_is_codes=x_is_codes)
    _obs_metrics.counter("exec.run.megakernel").inc()
    return _run_megakernel(plan, x, route)


def run_layers(
    plan: AnalogPlan,
    x: jax.Array,
    *,
    key: Optional[jax.Array] = None,
    x_is_codes: Optional[bool] = None,
) -> jax.Array:
    """Replay a lowered plan layer by layer: one analog dispatch per layer
    (per pass, for noisy split layers), the route :func:`run` takes when
    the megakernel's cannot be taken.  A block plan replays as its 4
    dispatches with the digital glue in jnp (:func:`_run_block_fallback`).
    Bit-exact against the megakernel route where both can run."""
    _obs_metrics.counter("exec.run.per_layer").inc()
    if plan.block is not None:
        return _run_block_fallback(plan, x, key)
    cfg = plan.cfg
    n = len(plan.layers)
    if x_is_codes is None:
        x_is_codes = plan.expects_codes
    ks = list(jax.random.split(key, n)) if key is not None else [None] * n
    is_codes = x_is_codes
    h = x
    for i, (lp, k) in enumerate(zip(plan.layers, ks)):
        h = run_layer(lp, h, cfg, key=k, x_is_codes=is_codes)
        if lp.epilogue == EPILOGUE_NONE and i < n - 1:
            # float hand-off between layers: ReLU in the float domain,
            # next layer re-quantizes (legacy inter-layer glue semantics)
            h = jax.nn.relu(h)
            is_codes = False
        else:
            is_codes = lp.epilogue == EPILOGUE_RELU_SHIFT
        if lp.flatten_out:
            # flatten only the layer's trailing output dims: merge the
            # position axis into the feature axis, PRESERVING any leading
            # batch dims (the old `h.reshape(h.shape[0], -1)` mangled
            # unbatched [K] inputs and multi-dim batches)
            h = h.reshape(h.shape[:-2] + (-1,))
    return h
