"""Mixture-of-Experts layers.

Two layers live here:

- ``moe_apply``: top-k softmax routing with capacity-bounded sort-based
  dispatch and batched expert GEMMs, experts sharded over the ``model``
  mesh axis (expert parallelism).  It DROPS tokens over capacity
  (capacity factor c), so it serves training and the dry-run cells:

    1. router logits -> top-k experts + normalized weights per token
    2. position-in-expert via a stable sort over expert ids
    3. scatter tokens into a [E, C, d] buffer (over-capacity tokens drop)
    4. einsum expert GEMMs, gather back with combine weights

  A dense einsum fallback (``dense=True``) exists for tiny smoke configs
  where sort/scatter overhead dwarfs the compute.

- ``held_moe_apply``: the served layer of one chip's share of an
  expert-parallel deployment (LFM2).  It is told which experts it holds
  (``params["held"]``, data), routes over ALL experts with the published
  router (sigmoid scores, top-k on score plus a selection bias, weights
  normalized over the chosen k), and computes only its held experts'
  part of the result.  It is dropless: the routed (token, held expert)
  pairs are sorted into per-expert groups of whole row tiles
  (:func:`held_rows`) and every pair is computed, by one grouped analog
  dispatch per expert matrix (:func:`repro.exec.run.run_expert_rows`).

Analog mapping (DESIGN.md §5): each expert's FFN matrices are analog tile
grids; EP places whole experts (= disjoint tile sets) on distinct devices,
exactly the paper's "individual layers partitioned into chip-sized chunks
executed in parallel" (§II-D) generalized to the expert dimension.  Held
experts are ordinary analog layers stacked on a leading expert axis, each
with its own fixed pattern, gain and static input LSB; the capacity
layer's raw expert arrays keep no fixed pattern.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.analog import AnalogConfig
from repro.core.noise import NoiseConfig
from repro.distributed.sharding import constrain
from repro.models import layers as L


def moe_init(key, d_model, d_ff, n_experts, *, n_shared=0, act="swiglu",
             noise: NoiseConfig = NoiseConfig(), dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    shape_up = (n_experts, d_model, d_ff)
    shape_down = (n_experts, d_ff, d_model)
    s_up = 1.0 / jnp.sqrt(d_model)
    s_down = 1.0 / jnp.sqrt(d_ff)
    p = {
        "router": {"w": (jax.random.normal(ks[0], (d_model, n_experts))
                         * s_up).astype(jnp.float32)},
        "up": (jax.random.normal(ks[1], shape_up) * s_up).astype(dtype),
        "down": (jax.random.normal(ks[2], shape_down) * s_down).astype(dtype),
    }
    if act == "swiglu":
        p["gate"] = (jax.random.normal(ks[3], shape_up) * s_up).astype(dtype)
    if n_shared:
        p["shared"] = L.mlp_init(
            jax.random.fold_in(key, 7), d_model, d_ff * n_shared, act=act,
            noise=noise, dtype=dtype,
        )
    return p


def moe_specs(*, act="swiglu", n_shared=0,
              noise: NoiseConfig = NoiseConfig()):
    p = {
        "router": {"w": (None, None)},
        "up": ("expert", "embed", None),
        "down": ("expert", None, "embed"),
    }
    if act == "swiglu":
        p["gate"] = ("expert", "embed", None)
    if n_shared:
        p["shared"] = L.mlp_specs(act=act, noise=noise)
    return p


def moe_module_spec(d_model, d_ff, n_experts, *, top_k, act="swiglu",
                    n_shared=0, capacity_factor: float = 1.25,
                    dense: bool = False,
                    noise: NoiseConfig = NoiseConfig()):
    """Declare one MoE layer for the api front door:
    ``api.compile(moe_module_spec(...), params, run)`` lowers every
    expert weight stack ONCE at compile time (``expert_stack`` fusion
    groups -> per-expert plans: weight codes, column scales and analog
    gains baked, zero lowering work per call) and
    ``CompiledModel.apply(x, key=)`` is :func:`moe_apply` over the
    pre-lowered tree.  ``params`` is :func:`moe_init`'s dict.  The
    router (and the shard_map expert-parallel dispatch, which slices raw
    weights per shard) keep their existing paths."""
    from repro import api

    def _apply(model, x, *, key=None, **kw):
        return moe_apply(model.lower(), x, acfg=model.acfg, top_k=top_k,
                         capacity_factor=capacity_factor, act=act,
                         dense=dense, key=key, **kw)

    names = ["up", "down"] + (["gate"] if act == "swiglu" else [])
    layers = [
        api.LayerSpec(n, d_ff if n == "down" else d_model,
                      d_model if n == "down" else d_ff,
                      stacked=n_experts)
        for n in names
    ]
    groups = tuple(
        api.GroupSpec(n, "expert_stack", (n,)) for n in names
    )
    return api.ModuleSpec(
        name=f"moe_{d_model}x{d_ff}x{n_experts}",
        kind="tree",
        apply_fn=_apply,
        layers=tuple(layers),
        groups=groups,
        param_axes=moe_specs(act=act, n_shared=n_shared, noise=noise),
    )


def _analog_expert_matmul(xe, w, acfg: AnalogConfig):
    """Per-expert analog matmul: xe [E, C, K] x w [E, K, N] with the BSS-2
    chunked saturating semantics (per-expert column scales + gain, signed
    inputs via split encoding).  Expert fixed-pattern noise is omitted (the
    rank-1 map would add O(E*(K+N)) state; documented in DESIGN.md).

    This is the PER-CALL path: weight codes, column scales and gains are
    re-derived inside every traced forward.  Compiling through
    :func:`moe_module_spec` replaces it with a pre-lowered
    ``expert_stack`` plan (:func:`repro.exec.lower.lower_expert_stack`,
    bit-exact, zero lowering work per call)."""
    from repro.core import quant
    from repro.core.analog import _statistical_gain, analog_matmul
    from repro.exec.lower import _count_lowering

    _count_lowering()
    xf = xe.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    a_scale = quant.act_scale_from_max(
        jax.lax.stop_gradient(jnp.abs(xf)).max() + 1e-9
    )
    w_scale = quant.weight_scale_from_max(
        jax.lax.stop_gradient(jnp.abs(wf)).max(axis=1, keepdims=True) + 1e-9
    )                                                        # [E, 1, N]
    w_code = quant.quantize_weight(wf, w_scale)
    gain = jax.vmap(lambda we: _statistical_gain(we, acfg.chunk_rows))(wf)
    inner = acfg.replace(use_pallas=False, signed_input="none")

    def one(a_e, w_e, g_e):
        return analog_matmul(a_e, w_e, g_e, None, None, inner)

    a_pos = quant.quantize_act(xf, a_scale)
    a_neg = quant.quantize_act(-xf, a_scale)
    y_int = jax.vmap(one)(a_pos, w_code, gain) - jax.vmap(one)(
        a_neg, w_code, gain
    )
    y = y_int * (a_scale * w_scale / gain[:, None, None])
    return y.astype(xe.dtype)


def _expert_matmul(xe, w, acfg: AnalogConfig, plan=None):
    """xe: [..., E, C, K] x w [E, K, N] -> [..., E, C, N].  ``plan`` (a
    pre-lowered ``expert_stack`` :class:`repro.exec.plan.GroupPlan`)
    replays the compile-time bake instead of re-deriving codes/gains per
    call - bit-exact vs the per-call path by construction."""
    if acfg.mode == "digital":
        return jnp.einsum("...eck,ekn->...ecn", xe, w.astype(xe.dtype))

    def one(x3):
        if plan is not None:
            from repro.exec.run import run_expert_stack

            return run_expert_stack(plan, x3, acfg)
        return _analog_expert_matmul(x3, w, acfg)

    if xe.ndim == 3:
        return one(xe)
    # fold leading group dims into capacity for the per-expert analog op
    lead = xe.shape[:-3]
    g = 1
    for v in lead:
        g *= v
    e, c, k = xe.shape[-3:]
    x3 = xe.reshape(g, e, c, k).transpose(1, 0, 2, 3).reshape(e, g * c, k)
    y3 = one(x3)
    n = y3.shape[-1]
    return (
        y3.reshape(e, g, c, n).transpose(1, 0, 2, 3).reshape(*lead, e, c, n)
    )


def _expert_ffn(params, xe, act, acfg: AnalogConfig):
    """xe: [E, C, d] -> [E, C, d] through the (analog) expert FFNs.
    A params tree compiled through ``api.compile(moe_module_spec(...))``
    carries pre-lowered ``expert_stack`` plans in ``params["_groups"]``
    (keyed by the member weight's name); raw params keep the per-call
    derivation."""
    from repro.exec.plan import find_group

    gps = params.get("_groups")
    plan_of = lambda n: find_group(gps, "expert_stack", (n,))
    up = _expert_matmul(xe, params["up"], acfg, plan=plan_of("up"))
    if act == "swiglu":
        gate = _expert_matmul(xe, params["gate"], acfg,
                              plan=plan_of("gate"))
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    return _expert_matmul(h, params["down"], acfg, plan=plan_of("down"))


def _expert_block_shard_map(params, buf_inputs, e, capacity, d, act, acfg):
    """Expert-parallel FFN with *explicit* collectives via shard_map.

    Each model shard builds the dispatch buffer for its LOCAL experts only
    (pure local scatter), runs the expert FFN on its expert shard, and the
    single collective is one all-gather of the expert outputs
    [B_loc, E, C, d] over the model axis (bwd = reduce-scatter).  This
    replaces GSPMD's choice of replicating the [B_loc, S*k, d] routed-copies
    tensor (measured 5 x 4 GiB f32 collectives per group on qwen3/train_4k;
    see EXPERIMENTS.md §Perf iteration 3)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    mesh = shd.get_mesh()
    x, st_, se, pos_c, keep = buf_inputs
    axes = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in axes) or None
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    e_loc = e // n_model

    def block(xb, st__, se_, pos_, keep_, up, gate, down):
        # xb: [B_loc, S, d] tokens (replicated over model); indices local
        xb = xb.astype(jnp.bfloat16)   # pin the gathered dtype to bf16
        midx = jax.lax.axis_index("model")
        se_loc = se_ - midx * e_loc
        valid = keep_ & (se_loc >= 0) & (se_loc < e_loc)
        se_c = jnp.clip(se_loc, 0, e_loc - 1)

        def scatter_one(xg, tg, sg, pg, vg):
            buf = jnp.zeros((e_loc, capacity, d), xg.dtype)
            return buf.at[sg, pg].add(jnp.where(vg[:, None], xg[tg], 0))

        buf = jax.vmap(scatter_one)(xb, st__, se_c, pos_, valid)
        p_loc = {"up": up, "down": down}
        if gate is not None:
            p_loc["gate"] = gate
        ye_loc = _expert_ffn(p_loc, buf, act, acfg)   # [B_loc, E_loc, C, d]
        # one explicit collective: gather every shard's expert outputs
        ye = jax.lax.all_gather(ye_loc, "model", axis=1, tiled=True)
        return ye                                      # [B_loc, E, C, d]

    gate = params.get("gate")
    in_specs = (
        P(batch_axes), P(batch_axes), P(batch_axes), P(batch_axes),
        P(batch_axes),
        P("model"), (P("model") if gate is not None else P()), P("model"),
    )
    fn = jax.shard_map(
        block, mesh=mesh,
        in_specs=in_specs,
        out_specs=P(batch_axes),
        check_vma=False,
    )
    return fn(x, st_, se, pos_c, keep, params["up"],
              gate if gate is not None else jnp.zeros((), x.dtype),
              params["down"])


def moe_apply(params, x, *, acfg: AnalogConfig, top_k: int,
              capacity_factor: float = 1.25, act="swiglu",
              dense: bool = False, dispatch: str = "gspmd_ep",
              key=None):
    """x: [B, S, d] -> (y, aux).  The batch dim doubles as the dispatch
    group (MaxText-style): all routing indices are group-local, so under
    GSPMD the scatter/gather shard over ``data`` while experts shard over
    ``model`` (EP) - no replicated [tokens, d] intermediates."""
    b, s, d = x.shape
    e = params["up"].shape[0]

    logits = x.astype(jnp.float32) @ params["router"]["w"]        # [B, S, E]
    probs = L.softmax(logits)
    topw, topi = jax.lax.top_k(probs, top_k)                      # [B, S, k]
    topw = topw / jnp.maximum(L.ordered_sum(topw), 1e-9)

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(
        1.0 / topi.size
    )
    aux = e * jnp.sum(me * ce)

    if dense:
        # smoke-config fallback: every expert sees every token
        t = b * s
        xf = x.reshape(t, d)
        w_full = jnp.zeros((t, e), jnp.float32).at[
            jnp.arange(t)[:, None], topi.reshape(t, top_k)
        ].set(topw.reshape(t, top_k))
        ye = _expert_ffn(
            params, jnp.broadcast_to(xf[None], (e, t, d)), act, acfg
        )
        y = jnp.einsum("te,etd->td", w_full, ye.astype(jnp.float32)).astype(
            x.dtype
        ).reshape(b, s, d)
    else:
        capacity = int(max(top_k, capacity_factor * s * top_k / e))
        eg = topi.reshape(b, s * top_k)
        wg = topw.reshape(b, s * top_k)

        def route(egg):
            """Group-local routing metadata: sorted expert ids, source
            token ids, positions-in-expert, keep mask."""
            order = jnp.argsort(egg, stable=True)
            se = egg[order]
            st_ = order // top_k
            pos_global = jnp.arange(se.shape[0])
            seg_start = jnp.full(
                (e,), se.shape[0], pos_global.dtype
            ).at[se].min(pos_global)
            pos = pos_global - seg_start[se]
            keep = pos < capacity
            pos_c = jnp.where(keep, pos, capacity - 1).astype(jnp.int32)
            return se, st_, pos_c, keep, order

        se, st_, pos_c, keep, order = jax.vmap(route)(eg)
        sw = jnp.take_along_axis(wg, order, axis=1)

        from repro.distributed import sharding as shd

        mesh = shd.get_mesh()
        use_sm = (
            dispatch == "shard_map"
            and mesh is not None
            and "model" in mesh.axis_names
        )
        if use_sm:
            ye = _expert_block_shard_map(
                params, (x, st_, se, pos_c, keep), e, capacity, d, act, acfg
            )
        else:
            def scatter_one(xg, tg, sg, pg, kg):
                buf = jnp.zeros((e, capacity, d), xg.dtype)
                return buf.at[sg, pg].add(
                    jnp.where(kg[:, None], xg[tg], 0)
                )

            buf = jax.vmap(scatter_one)(x, st_, se, pos_c, keep)
            if dispatch == "replicated_buf":
                # (refuted variant, kept for the §Perf log)
                buf = constrain(buf, "batch", None, None, None)
            else:
                buf = constrain(buf, "batch", "expert", "capacity", None)
            ye = _expert_ffn(params, buf, act, acfg)      # [B, E, C, d]
            ye = constrain(ye, "batch", "expert", "capacity", None)

        def combine_one(yeg, seg, stg, pcg, kg, swg):
            contrib = yeg[seg, pcg] * jnp.where(kg, swg, 0.0)[:, None].astype(
                x.dtype
            )
            return jnp.zeros((s, d), x.dtype).at[stg].add(
                contrib.astype(x.dtype)
            )

        y = jax.vmap(combine_one)(ye, se, st_, pos_c, keep, sw)

    if "shared" in params:
        y = y + L.mlp_apply(params["shared"], x, acfg, act=act, key=key)
    return y, aux


# ------------------------------------------------------------------
# held-expert layer (one chip's share, dropless)
# ------------------------------------------------------------------
_EXPERT_MATS = ("up", "gate", "down")


def held_moe_init(key, d_model, d_ff, n_experts, n_held, *,
                  noise: NoiseConfig = NoiseConfig(), dtype=jnp.float32):
    """Router over all ``n_experts``, a zero selection bias, and the first
    ``n_held`` experts as stacked analog SwiGLU layers (``held`` names
    their ids)."""
    ks = jax.random.split(key, 4)

    def stack(k, din, dout):
        return jax.vmap(lambda kk: L.linear_init(
            kk, din, dout, noise=noise, dtype=dtype))(
                jax.random.split(k, n_held))

    return {
        "router": {"w": (jax.random.normal(ks[0], (d_model, n_experts))
                         / jnp.sqrt(d_model)).astype(jnp.float32)},
        "expert_bias": jnp.zeros((n_experts,), jnp.float32),
        # ids as float32 (exact below 2**24): a params leaf the gradient
        # passes over (the optimizer freezes it)
        "held": jnp.arange(n_held, dtype=jnp.float32),
        "experts": {"up": stack(ks[1], d_model, d_ff),
                    "gate": stack(ks[2], d_model, d_ff),
                    "down": stack(ks[3], d_ff, d_model)},
    }


def held_moe_specs(noise: NoiseConfig = NoiseConfig()):
    def stacked(a, b):
        return jax.tree.map(
            lambda s: ("expert",) + s, L.linear_specs(a, b, noise=noise),
            is_leaf=lambda x: isinstance(x, tuple))

    return {
        "router": {"w": (None, None)},
        "expert_bias": (None,),
        "held": (None,),
        "experts": {"up": stacked("embed", "mlp"),
                    "gate": stacked("embed", "mlp"),
                    "down": stacked("mlp", "embed")},
    }


def held_moe_module_spec(d_model, d_ff, n_held, *, top_k: int,
                         noise: NoiseConfig = NoiseConfig()):
    """Declare one held-expert layer for the api front door: each of the
    three expert matrices is an analog layer stacked over the held
    experts (lowered once, per expert, with its own fixed pattern), and
    ``CompiledModel.apply(x)`` is :func:`held_moe_apply` over the
    pre-lowered tree."""
    from repro import api

    def _apply(model, x, **kw):
        return held_moe_apply(model.lower(), x, acfg=model.acfg,
                              top_k=top_k, **kw)

    dims = {"up": (d_model, d_ff), "gate": (d_model, d_ff),
            "down": (d_ff, d_model)}
    return api.ModuleSpec(
        name=f"held_moe_{d_model}x{d_ff}x{n_held}",
        kind="tree",
        apply_fn=_apply,
        layers=tuple(api.LayerSpec(f"experts.{m}", *dims[m], stacked=n_held)
                     for m in _EXPERT_MATS),
        param_axes=held_moe_specs(noise),
    )


def route(params, u, top_k: int):
    """Published LFM2 routing over every expert, fp32 at ``HIGHEST``:
    ``s = sigmoid(u W_r)``, the top-k of ``s + expert_bias`` chosen, their
    weights ``s / (sum of the chosen s + 1e-6)`` (summed in
    :func:`repro.models.layers.ordered_sum`'s order).  ``u [T, d]`` ->
    (chosen expert ids ``[T, k]``, weights ``[T, k]``)."""
    logits = jnp.einsum("td,de->te", u.astype(jnp.float32),
                        params["router"]["w"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(s + params["expert_bias"], top_k)
    w = jnp.take_along_axis(s, sel, axis=-1)
    return sel, w / (L.ordered_sum(w) + 1e-6)


def tile_rows(n_tokens: int, top_k: int, n_held: int) -> int:
    """Rows per tile of the grouped expert dispatch: the expected group
    size rounded up to a power of two, between 8 and 128 (prefill groups
    fill 128-row tiles, a decode step's few rows take 8-row tiles)."""
    per = -(-n_tokens * min(top_k, n_held) // n_held)
    return int(min(128, max(8, 1 << max(per - 1, 0).bit_length())))


def held_rows(sel, held, block_m: int):
    """Sort the routed (token, held expert) pairs into per-expert groups
    padded to whole ``block_m``-row tiles.  ``sel [T, k]`` chosen ids,
    ``held [H]`` held ids.  Returns (:class:`repro.exec.run.ExpertRows`,
    each pair's row ``[T, k]`` (meaningful where held), each pair's held
    mask ``[T, k]``, the token of each row ``[R]``, pairs per held expert
    ``[H]``).  The row count is static: every pair of every token could
    be held, plus one partial tile per expert."""
    from repro.exec.run import ExpertRows

    t, k = sel.shape
    h = held.shape[0]
    match = sel[:, :, None] == held[None, None, :]           # [T, k, H]
    is_held = match.any(-1)
    hidx = jnp.argmax(match, axis=-1).reshape(-1)           # [T*k]
    onehot = match.reshape(t * k, h).astype(jnp.int32)
    counts = onehot.sum(0)                                   # [H]
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0) - onehot,
                               hidx[:, None], axis=1)[:, 0]
    tiles = (counts + block_m - 1) // block_m
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    live = tile_end[-1]
    g = -(-t * min(k, h) // block_m) + h
    r = g * block_m
    row = jnp.where(is_held.reshape(-1), tile_start[hidx] * block_m + rank,
                    r)
    token = jnp.zeros((r,), jnp.int32).at[row].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
    row_live = jnp.zeros((r,), bool).at[row].set(True, mode="drop")
    last = jnp.maximum(live, 1) - 1
    tile_expert = jnp.searchsorted(
        tile_end, jnp.minimum(jnp.arange(g), last), side="right")
    tile_expert = jnp.minimum(tile_expert, h - 1).astype(jnp.int32)
    rows = ExpertRows(row_live=row_live, tile_expert=tile_expert,
                      live_tiles=live.reshape(1).astype(jnp.int32),
                      block_m=block_m)
    return rows, row.reshape(t, k), is_held, token, counts


def _expert_plan(node, acfg: AnalogConfig):
    """The stacked layer plan of one expert matrix: the compile-time bake
    where ``api.compile`` left one, else lowered here (per call, the
    training contract)."""
    lp = node.get("_plan")
    if lp is not None:
        return lp
    from repro.exec.lower import lower_layer

    return jax.vmap(lambda p: lower_layer(p, acfg))(node)


def held_moe_apply(params, x, *, acfg: AnalogConfig, top_k: int,
                   key=None):
    """x: [B, S, d] -> (y, stats).  ``stats``: ``rows`` [H] routed pairs
    per held expert, ``tiles`` row tiles the grouped dispatch ran.

    Digital mode runs every held expert over every token (exact, for the
    training and smoke paths); analog modes run only the routed rows."""
    del key
    b, s, d = x.shape
    t = b * s
    u = x.reshape(t, d)
    held = params["held"].astype(jnp.int32)
    ex = params["experts"]
    with jax.named_scope("moe.route"):
        sel, w = route(params, u, top_k)
        block_m = tile_rows(t, top_k, held.shape[0])
        rows, row, is_held, token, counts = held_rows(sel, held, block_m)
    with jax.named_scope("moe.experts"):
        if acfg.mode == "digital":
            ye = jax.vmap(lambda p: L.mlp_apply(p, u, acfg))(ex)  # [H,T,d]
            hidx = jnp.argmax(sel[:, :, None] == held[None, None, :], -1)
            parts = ye[hidx, jnp.arange(t)[:, None]]           # [T, k, d]
        else:
            from repro.exec.run import run_expert_rows as run_rows

            xr = u[token]
            up = run_rows(_expert_plan(ex["up"], acfg), xr, rows, acfg)
            gate = run_rows(_expert_plan(ex["gate"], acfg), xr, rows, acfg)
            hr = jax.nn.silu(gate) * up
            yr = run_rows(_expert_plan(ex["down"], acfg), hr, rows, acfg)
            parts = yr[jnp.minimum(row, yr.shape[0] - 1)]      # [T, k, d]
        y = jnp.zeros((t, d), jnp.float32)
        for j in range(top_k):        # in selection order, as the reference
            y = y + jnp.where(is_held[:, j, None],
                              w[:, j, None] * parts[:, j].astype(
                                  jnp.float32), 0.0)
    stats = {"rows": counts.astype(jnp.int32),
             "tiles": rows.live_tiles[0]}
    return y.reshape(b, s, d).astype(x.dtype), stats
