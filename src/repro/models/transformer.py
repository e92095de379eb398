"""Generic decoder LM covering all 10 assigned architectures.

Layers are grouped into homogeneous *scan groups* (dense: 1 layer/group;
Llama-4: [dense, moe] pairs; Zamba2: shared-attn + 6 mamba layers) and
scanned with stacked parameters, so HLO size and compile time are O(1) in
depth - mandatory for the 40-cell dry-run on one host.  A config with a
per-layer kind list (``ArchConfig.layer_kinds``: LFM2's short-conv and
attention layers, dense and held-expert feed-forwards) has no repeating
group: its layers are kept unstacked (``params["layers"]["l<i>"]``) and
applied in order, each with its own kind of cache state.

Every parameter matmul dispatches through the analog backend
(repro.core.analog); the execution mode (digital / analog_faithful /
analog_fast) is a RunConfig knob, making the paper's technique a
first-class, globally-switchable execution backend.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RunConfig
from repro.core.noise import NoiseConfig
from repro.distributed.sharding import constrain
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rwkv as R
from repro.models import shortconv as C
from repro.models import ssm as S

NOISE = NoiseConfig()  # module-level default; configs may override later


# ----------------------------------------------------------- group layout
def group_def(cfg: ArchConfig) -> list[str]:
    """Kinds of the layers inside one scan group."""
    if cfg.layer_kinds:
        return list(cfg.layer_kinds)
    if cfg.block == "mamba" and cfg.attn_every:
        return ["mamba"] * cfg.attn_every          # + shared attn at entry
    if cfg.n_experts and cfg.moe_every > 1:
        return [cfg.layer_kind(i) for i in range(cfg.moe_every)]
    return [cfg.layer_kind(0)]


def n_groups(cfg: ArchConfig) -> int:
    g = len(group_def(cfg))
    assert cfg.n_layers % g == 0, (cfg.n_layers, g)
    return cfg.n_layers // g


# ------------------------------------------------------------------ init
_MIXED = ("attn_mlp", "attn_moe", "conv_mlp", "conv_moe")


def _layer_init(key, kind: str, cfg: ArchConfig):
    dtype = cfg.dtype
    ks = jax.random.split(key, 2)
    p = {"ln1": L.norm_init(cfg.d_model, cfg.norm)}
    if kind in _MIXED:
        if kind.startswith("conv_"):
            p["conv"] = C.shortconv_init(ks[0], cfg.d_model, cfg.conv_taps,
                                         noise=NOISE, dtype=dtype)
        else:
            p["attn"] = A.attention_init(
                ks[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                noise=NOISE, dtype=dtype, qk_norm=cfg.qk_norm,
            )
        p["ln2"] = L.norm_init(cfg.d_model, cfg.norm)
        if kind.endswith("_mlp"):
            ff = cfg.moe_dense_d_ff or cfg.d_ff
            p["mlp"] = L.mlp_init(ks[1], cfg.d_model, ff, act=cfg.act,
                                  noise=NOISE, dtype=dtype)
        elif cfg.held_experts:
            p["moe"] = M.held_moe_init(
                ks[1], cfg.d_model, cfg.moe_d_ff, cfg.n_experts,
                cfg.held_experts, noise=NOISE, dtype=dtype,
            )
        else:
            p["moe"] = M.moe_init(
                ks[1], cfg.d_model, cfg.moe_d_ff, cfg.n_experts,
                n_shared=cfg.n_shared_experts, act=cfg.act, noise=NOISE,
                dtype=dtype,
            )
    elif kind == "rwkv":
        p["rwkv"] = R.rwkv_init(ks[0], cfg.d_model, cfg.n_heads,
                                d_ff=cfg.d_ff, noise=NOISE, dtype=dtype)
        p["ln2"] = L.norm_init(cfg.d_model, cfg.norm)
        p["cmix"] = R.channel_mix_init(ks[1], cfg.d_model, cfg.d_ff,
                                       noise=NOISE, dtype=dtype)
    elif kind == "mamba":
        p["mamba"] = S.mamba_init(ks[0], cfg.d_model, d_state=cfg.ssm_state,
                                  noise=NOISE, dtype=dtype)
    else:
        raise ValueError(kind)
    return p


def _layer_specs(kind: str, cfg: ArchConfig):
    p = {"ln1": L.norm_specs(cfg.norm)}
    if kind in _MIXED:
        if kind.startswith("conv_"):
            p["conv"] = C.shortconv_specs(NOISE)
        else:
            p["attn"] = A.attention_specs(NOISE, qk_norm=cfg.qk_norm)
        p["ln2"] = L.norm_specs(cfg.norm)
        if kind.endswith("_mlp"):
            p["mlp"] = L.mlp_specs(act=cfg.act, noise=NOISE)
        elif cfg.held_experts:
            p["moe"] = M.held_moe_specs(NOISE)
        else:
            p["moe"] = M.moe_specs(act=cfg.act,
                                   n_shared=cfg.n_shared_experts, noise=NOISE)
    elif kind == "rwkv":
        p["rwkv"] = R.rwkv_specs(NOISE)
        p["ln2"] = L.norm_specs(cfg.norm)
        p["cmix"] = R.channel_mix_specs(NOISE)
    elif kind == "mamba":
        p["mamba"] = S.mamba_specs(NOISE)
    return p


def _group_init(key, cfg: ArchConfig):
    kinds = group_def(cfg)
    ks = jax.random.split(key, len(kinds))
    return {f"l{i}": _layer_init(ks[i], kind, cfg)
            for i, kind in enumerate(kinds)}


def lm_init(key, cfg: ArchConfig):
    ng = n_groups(cfg)
    k_emb, k_layers, k_head, k_attn = jax.random.split(key, 4)
    params = {}
    if cfg.embed_inputs:
        params["embed"] = L.embedding_init(k_emb, cfg.vocab_size, cfg.d_model,
                                           dtype=cfg.dtype)
    if cfg.layer_kinds:
        params["layers"] = _group_init(k_layers, cfg)     # unstacked
    else:
        params["layers"] = jax.vmap(
            lambda k: _group_init(k, cfg)
        )(jax.random.split(k_layers, ng))
    if cfg.attn_every:   # zamba2 shared attention block (single param set)
        params["shared_attn"] = {
            "ln": L.norm_init(cfg.d_model, cfg.norm),
            "attn": A.attention_init(
                k_attn, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                noise=NOISE, dtype=cfg.dtype,
            ),
        }
    params["final_norm"] = L.norm_init(cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.linear_init(
            k_head, cfg.d_model, cfg.vocab_size, noise=NOISE, dtype=cfg.dtype
        )
    return params


def _prepend(specs, name="layers"):
    return jax.tree.map(
        lambda s: (name,) + s,
        specs,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )


def lm_module_spec(cfg: ArchConfig, params):
    """Declare the LM's analog layers once for the api front door:
    ``api.compile(lm_module_spec(cfg, params), params, run)`` bakes every
    parameter matmul - attention QKV fused into one dispatch group per
    (scan-stacked) layer - and ``CompiledModel.apply(batch, cache=, rng=)``
    is :func:`lm_apply` over the pre-lowered tree.  ``params`` may be
    abstract (only shapes are read)."""
    from repro import api

    def _apply(model, batch, *, cache=None, rng=None):
        return lm_apply(model.lower(), batch, cfg, model.run_cfg,
                        cache=cache, rng=rng)

    return api.tree_spec(f"lm_{cfg.name}", params, param_axes=lm_specs(cfg),
                         apply_fn=_apply)


def lm_specs(cfg: ArchConfig):
    kinds = group_def(cfg)
    specs = {}
    if cfg.embed_inputs:
        specs["embed"] = L.embedding_specs()
    group = {f"l{i}": _layer_specs(kind, cfg) for i, kind in enumerate(kinds)}
    specs["layers"] = group if cfg.layer_kinds else _prepend(group)
    if cfg.attn_every:
        specs["shared_attn"] = {
            "ln": L.norm_specs(cfg.norm),
            "attn": A.attention_specs(NOISE),
        }
    specs["final_norm"] = L.norm_specs(cfg.norm)
    if not cfg.tie_embeddings:
        specs["lm_head"] = L.linear_specs("embed", "vocab", noise=NOISE)
    return specs


# ------------------------------------------------------------------ apply
def _layer_apply(p, kind, x, *, cfg, run, positions, cache, key, window=None):
    acfg = run.analog
    new_cache = {}
    if kind == "attn_mlp":
        bp = p.get("_block_plan")
        if (bp is not None and cache is None and window is None
                and not cfg.mrope and x.shape[1] == bp.block.seq):
            # pre-lowered fused block plan (attach_block_plans): the
            # whole attention+MLP block replays as ONE megakernel
            # dispatch.  Static-prefill only - the baked in-kernel
            # attention assumes positions 0..seq-1 and no cache; decode
            # and other lengths keep the per-layer model path below.
            from repro.exec.run import run as run_plan

            return run_plan(bp, x, key=key), None, 0.0
    aux = 0.0
    if kind in _MIXED:
        h = L.norm_apply(p["ln1"], x, cfg.norm)
        if kind.startswith("conv_"):
            y, c = C.shortconv_apply(
                p["conv"], h, acfg=acfg,
                state=None if cache is None else cache["conv"], key=key,
            )
            mixer = "conv"
        else:
            y, c = A.attention_apply(
                p["attn"], h, positions=positions, acfg=acfg,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.hd, rope_theta=cfg.rope_theta, mrope=cfg.mrope,
                cache=None if cache is None else cache["attn"],
                window=window, attn_cp=getattr(run, "attn_cp", "auto"),
                key=key,
            )
            mixer = "attn"
        x = x + y.astype(x.dtype)
        if cache is not None:
            new_cache[mixer] = c
        h = L.norm_apply(p["ln2"], x, cfg.norm)
        if kind.endswith("_mlp"):
            y = L.mlp_apply(p["mlp"], h, acfg, act=cfg.act, key=key)
        elif cfg.held_experts:
            y, stats = M.held_moe_apply(p["moe"], h, acfg=acfg,
                                        top_k=cfg.top_k, key=key)
            if cache is not None:
                new_cache["moe"] = stats
        else:
            y, aux = M.moe_apply(
                p["moe"], h, acfg=acfg, top_k=cfg.top_k,
                capacity_factor=run.capacity_factor, act=cfg.act,
                dispatch=getattr(run, "moe_dispatch", "gspmd_ep"), key=key,
            )
        x = x + y.astype(x.dtype)
    elif kind == "rwkv":
        h = L.norm_apply(p["ln1"], x, cfg.norm)
        y, c1 = R.rwkv_apply(
            p["rwkv"], h, acfg=acfg, n_heads=cfg.n_heads,
            cache=None if cache is None else cache["tmix"], key=key,
        )
        x = x + y.astype(x.dtype)
        h = L.norm_apply(p["ln2"], x, cfg.norm)
        y, c2 = R.channel_mix_apply(
            p["cmix"], h, acfg=acfg,
            cache=None if cache is None else cache["cmix"], key=key,
        )
        x = x + y.astype(x.dtype)
        if cache is not None:
            new_cache = {"tmix": c1, "cmix": c2}
    elif kind == "mamba":
        h = L.norm_apply(p["ln1"], x, cfg.norm)
        y, c = S.mamba_apply(
            p["mamba"], h, acfg=acfg, d_state=cfg.ssm_state,
            cache=None if cache is None else cache["mamba"], key=key,
        )
        x = x + y.astype(x.dtype)
        if cache is not None:
            new_cache["mamba"] = c
    return x, (new_cache if cache is not None else None), aux


def _group_apply(gp, x, *, cfg, run, positions, shared_attn, cache, key):
    kinds = group_def(cfg)
    aux_total = 0.0
    new_cache = {} if cache is not None else None
    if shared_attn is not None:
        h = L.norm_apply(shared_attn["ln"], x, cfg.norm)
        y, c = A.attention_apply(
            shared_attn["attn"], h, positions=positions, acfg=run.analog,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
            rope_theta=cfg.rope_theta,
            cache=None if cache is None else cache["shared_attn"],
            attn_cp=getattr(run, "attn_cp", "auto"), key=key,
        )
        x = x + y.astype(x.dtype)
        if cache is not None:
            new_cache["shared_attn"] = c
    for i, kind in enumerate(kinds):
        sub_key = None if key is None else jax.random.fold_in(key, i)
        x, c, aux = _layer_apply(
            gp[f"l{i}"], kind, x, cfg=cfg, run=run, positions=positions,
            cache=None if cache is None else cache[f"l{i}"], key=sub_key,
        )
        aux_total = aux_total + aux
        if cache is not None:
            new_cache[f"l{i}"] = c
    return x, new_cache, aux_total


def _scan_groups(params, x, *, cfg, run, positions, layer_cache, rng,
                 cached):
    """The stacked scan groups of ``params["layers"]`` over ``x``."""
    shared = params.get("shared_attn")
    keys = (
        None
        if rng is None
        else jax.random.split(rng, n_groups(cfg))
    )

    def body(carry, inp):
        x, aux = carry
        gp, gc, gk = inp
        fn = _group_apply
        if cfg.remat and not cached:
            fn = jax.checkpoint(
                functools.partial(
                    _group_apply, cfg=cfg, run=run, positions=positions,
                    shared_attn=shared,
                ),
                static_argnums=(),
            )
            x2, nc, aux_g = fn(gp, x, cache=gc, key=gk)
        else:
            x2, nc, aux_g = fn(gp, x, cfg=cfg, run=run, positions=positions,
                               shared_attn=shared, cache=gc, key=gk)
        # sequence-parallel residual carry (Megatron-SP): activations saved
        # across scan groups for backward shard their seq axis over the
        # model axis -> 16x less checkpointed-residual HBM
        x2 = constrain(x2, "batch", "seq_sp", None)
        return (x2, aux + aux_g), nc

    (x, aux), new_layer_cache = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (params["layers"], layer_cache, keys),
    )
    return x, new_layer_cache, aux


def lm_apply(params, batch, cfg: ArchConfig, run: RunConfig, *,
             cache=None, rng=None, last_only: bool = False):
    """batch: {"tokens": [B,S] int32} or {"embeds": [B,S,d]}, optional
    {"positions": [B,S] or [B,S,3]}.  Returns (logits, new_cache, aux).
    ``last_only`` computes the logits of the last position alone
    (``[B, 1, vocab]``)."""
    acfg = run.analog
    adt = jnp.bfloat16 if run.activation_dtype == "bfloat16" else jnp.float32
    if cfg.embed_inputs:
        x = L.embedding_apply(params["embed"], batch["tokens"])
    else:
        x = batch["embeds"]
    x = x.astype(adt)
    b, s = x.shape[:2]
    x = constrain(x, "batch", "seq", None)

    if "positions" in batch:
        positions = batch["positions"]
    else:
        start = cache["step"] if cache is not None else 0
        pos = start + jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(pos, (b, s))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[..., None], (b, s, 3))

    layer_cache = None if cache is None else cache["layers"]
    if cfg.layer_kinds:      # unstacked layers of mixed kinds, in order
        x, new_layer_cache, aux = _group_apply(
            params["layers"], x, cfg=cfg, run=run, positions=positions,
            shared_attn=None, cache=layer_cache, key=rng,
        )
    else:
        x, new_layer_cache, aux = _scan_groups(
            params, x, cfg=cfg, run=run, positions=positions,
            layer_cache=layer_cache, rng=rng, cached=cache is not None,
        )
    if last_only:
        x = x[:, -1:]

    x = L.norm_apply(params["final_norm"], x, cfg.norm)
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "bsd,vd->bsv", x, params["embed"]["table"].astype(x.dtype)
        )
    else:
        logits = L.linear_apply(params["lm_head"], x, acfg, key=rng)
    # logits stay in the activation dtype (bf16): at [tokens, vocab] scale
    # the f32 copy dominates HBM (3 GiB/device on llama4/train_4k); the
    # loss computes its softmax reductions in f32
    logits = constrain(logits, "batch", "seq", "vocab")
    new_cache = None
    if cache is not None:
        new_cache = {"layers": new_layer_cache, "step": cache["step"] + s}
    return logits, new_cache, aux


def attach_block_plans(params, cfg: ArchConfig, acfg, *, seq: int):
    """Pre-lower every ``attn_mlp`` block of an LM into a fused
    attention+MLP megakernel plan and attach it as a ``"_block_plan"``
    leaf beside the block's parameters.  ``lm_apply`` then replays each
    of those blocks as ONE analog dispatch on static prefills of length
    ``seq`` (no cache, default positions); decode and other lengths keep
    the per-layer path untouched.

    The LM's scan groups hold stacked parameters, so the lowering is
    vmapped over the group axis - the attached plan's leaves carry the
    same leading stack dim and are sliced per group by the scan, while
    the static schedule is shared (one compiled kernel for all groups).

    ``acfg`` must be megakernel-eligible (``act_calib == "static"``,
    none/split signed encoding - see
    :func:`repro.exec.lower.lower_block`); the architecture must use the
    glue the kernel bakes (rmsnorm + swiglu, plain RoPE).
    """
    if cfg.norm != "rmsnorm" or cfg.act != "swiglu" or cfg.mrope:
        raise ValueError(
            "attach_block_plans: the fused block kernel bakes rmsnorm + "
            f"swiglu + plain RoPE glue; got norm={cfg.norm!r}, "
            f"act={cfg.act!r}, mrope={cfg.mrope}"
        )
    from repro.exec.lower import lower_block

    kinds = group_def(cfg)
    new_layers = dict(params["layers"])
    for i, kind in enumerate(kinds):
        if kind != "attn_mlp":
            continue
        node = new_layers[f"l{i}"]
        block = {k: node[k] for k in ("ln1", "attn", "ln2", "mlp")}
        plan = jax.vmap(
            lambda p: lower_block(
                p, acfg, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.hd, seq=seq, rope_theta=cfg.rope_theta,
            )
        )(block)
        new_layers[f"l{i}"] = {**node, "_block_plan": plan}
    return {**params, "layers": new_layers}


# ------------------------------------------------------------------ cache
def _layer_cache(kind, cfg: ArchConfig, batch, max_len, dtype=jnp.bfloat16):
    if kind in _MIXED:
        if kind.startswith("conv_"):
            # the last taps-1 gated inputs of the short convolution
            c = {"conv": jnp.zeros((batch, cfg.conv_taps - 1, cfg.d_model),
                                   jnp.float32)}
        else:
            c = {"attn": A.init_cache(batch, max_len, cfg.n_kv_heads, cfg.hd,
                                      dtype)}
        if kind.endswith("_moe") and cfg.held_experts:
            # the call's routed pairs per held expert and the grouped
            # dispatch's row tiles (read by the serve engine)
            c["moe"] = {"rows": jnp.zeros((cfg.held_experts,), jnp.int32),
                        "tiles": jnp.zeros((), jnp.int32)}
        return c
    if kind == "rwkv":
        hd = cfg.d_model // cfg.n_heads
        return {
            "tmix": {
                "x_prev": jnp.zeros((batch, cfg.d_model), dtype),
                "state": jnp.zeros((batch, cfg.n_heads, hd, hd), jnp.float32),
            },
            "cmix": {"x_prev": jnp.zeros((batch, cfg.d_model), dtype)},
        }
    if kind == "mamba":
        d_in = 2 * cfg.d_model
        nh = d_in // 64
        return {
            "mamba": {
                "conv": jnp.zeros(
                    (batch, S.CONV_K - 1, d_in + 2 * cfg.ssm_state),
                    jnp.float32,
                ),
                "state": jnp.zeros((batch, nh, 64, cfg.ssm_state),
                                   jnp.float32),
            }
        }
    raise ValueError(kind)


def init_lm_cache(cfg: ArchConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    kinds = group_def(cfg)
    group = {
        f"l{i}": _layer_cache(kind, cfg, batch, max_len, dtype)
        for i, kind in enumerate(kinds)
    }
    if cfg.layer_kinds:
        return {"layers": group, "step": jnp.zeros((), jnp.int32)}
    if cfg.attn_every:
        group["shared_attn"] = A.init_cache(batch, max_len, cfg.n_kv_heads,
                                            cfg.hd, dtype)
    ng = n_groups(cfg)
    stacked = jax.tree.map(
        lambda leaf: jnp.zeros((ng,) + leaf.shape, leaf.dtype), group
    )
    return {"layers": stacked, "step": jnp.zeros((), jnp.int32)}


def _layer_cache_specs(kind, dtype=jnp.bfloat16, cfg=None):
    if kind in _MIXED:
        c = ({"conv": ("batch", None, None)} if kind.startswith("conv_")
             else {"attn": A.cache_specs(dtype)})
        if kind.endswith("_moe") and cfg is not None and cfg.held_experts:
            c["moe"] = {"rows": (None,), "tiles": ()}
        return c
    if kind == "rwkv":
        return {"tmix": R.rwkv_cache_specs(),
                "cmix": {"x_prev": ("batch", None)}}
    if kind == "mamba":
        return {"mamba": S.mamba_cache_specs()}
    raise ValueError(kind)


def lm_cache_specs(cfg: ArchConfig, dtype=jnp.bfloat16):
    kinds = group_def(cfg)
    group = {f"l{i}": _layer_cache_specs(kind, dtype, cfg)
             for i, kind in enumerate(kinds)}
    if cfg.layer_kinds:
        return {"layers": group, "step": ()}
    if cfg.attn_every:
        group["shared_attn"] = A.cache_specs(dtype)
    return {"layers": _prepend(group), "step": ()}


# ------------------------------------------------------------------- loss
def lm_loss(params, batch, cfg: ArchConfig, run: RunConfig, rng=None):
    """Next-token cross-entropy + MoE aux loss.  batch needs "labels"."""
    logits, _, aux = lm_apply(params, batch, cfg, run, rng=rng)
    labels = batch["labels"]
    # f32 reductions over bf16 logits: logsumexp upcasts internally
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits, labels[..., None], axis=-1
    )[..., 0].astype(jnp.float32)
    nll = logz - gold
    mask = batch.get("mask")
    if mask is not None:
        nll = nll * mask
        denom = jnp.maximum(mask.sum(), 1.0)
    else:
        denom = nll.size
    loss = nll.sum() / denom + 0.01 * aux
    metrics = {"nll": nll.sum() / denom, "aux": aux,
               "logit_z": (logz**2).mean()}
    return loss, metrics
