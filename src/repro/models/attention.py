"""Grouped-query attention with chunked online-softmax (flash-style) for
long prefill and a dense-cache decode path.

The parameter projections (QKV/O) run on the analog backend; the
activation x activation products (logits, AV) stay digital - the BSS-2
synapse array holds static weights only (DESIGN.md §5.1).
"""
from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.analog import AnalogConfig
from repro.core.noise import NoiseConfig
from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models.flash import flash_attention, flash_attention_cp

NEG_INF = -1e30


def attention_init(key, d_model, n_heads, n_kv_heads, head_dim, *,
                   noise: NoiseConfig = NoiseConfig(), dtype=jnp.float32,
                   qk_norm: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.linear_init(ks[0], d_model, n_heads * head_dim,
                            noise=noise, dtype=dtype),
        "wk": L.linear_init(ks[1], d_model, n_kv_heads * head_dim,
                            noise=noise, dtype=dtype),
        "wv": L.linear_init(ks[2], d_model, n_kv_heads * head_dim,
                            noise=noise, dtype=dtype),
        "wo": L.linear_init(ks[3], n_heads * head_dim, d_model,
                            noise=noise, dtype=dtype),
    }
    if qk_norm:      # RMSNorm over the head dim of q and of k (LFM2)
        p["q_norm"] = L.norm_init(head_dim)
        p["k_norm"] = L.norm_init(head_dim)
    return p


def attention_specs(noise: NoiseConfig = NoiseConfig(),
                    qk_norm: bool = False):
    p = {
        "wq": L.linear_specs("embed", "heads", noise=noise),
        "wk": L.linear_specs("embed", "heads", noise=noise),
        "wv": L.linear_specs("embed", "heads", noise=noise),
        "wo": L.linear_specs("heads", "embed", noise=noise),
    }
    if qk_norm:
        p["q_norm"] = L.norm_specs()
        p["k_norm"] = L.norm_specs()
    return p


# ----------------------------------------------------------- soft attention
def _dense_attention(q, k, v, *, causal: bool, q_offset=0,
                     window: Optional[int] = None):
    """q: [B,Sq,KVH,G,dh], k/v: [B,Sk,KVH,dh].  Direct path for short S."""
    dh = q.shape[-1]
    scale = 1.0 / jnp.sqrt(dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        # traced iota (not a concrete arange constant): this mask is also
        # built inside the fused-block pallas kernel, whose trace may not
        # capture constants
        qpos = jax.lax.broadcasted_iota(jnp.int32, (sq, 1), 0) + q_offset
        kpos = jax.lax.broadcasted_iota(jnp.int32, (1, sk), 1)
        mask = qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = L.softmax(s)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def prefill_attention_glue(qkv, *, batch: int, seq: int, n_heads: int,
                           n_kv_heads: int, head_dim: int,
                           rope_theta: float) -> jax.Array:
    """The pure digital glue between the fused QKV projection and the
    output projection for a STATIC prefill (positions ``0..seq-1``, no
    cache, dense causal attention): split the concatenated QKV columns,
    apply RoPE, group the query heads, attend.

    ``qkv``: ``[batch * seq, nq + 2 * nkv]`` (the column layout of the
    ``column_concat`` QKV group) -> ``[batch * seq, nq]``.

    This is THE single definition of that glue: ``attention_apply``'s
    dense prefill branch, the per-layer block fallback
    (``repro.exec.run._run_block_fallback``) and the in-kernel "attn"
    hand-off of the block megakernel
    (:mod:`repro.kernels.analog_plan`) all trace this same function, so
    their bit-exactness is by construction rather than by parallel
    implementations.
    """
    nq = n_heads * head_dim
    nkv = n_kv_heads * head_dim
    g = n_heads // n_kv_heads
    qkv = qkv.reshape(batch, seq, nq + 2 * nkv)
    q, k, v = jnp.split(qkv, [nq, nq + nkv], axis=-1)
    q = q.reshape(batch, seq, n_heads, head_dim)
    k = k.reshape(batch, seq, n_kv_heads, head_dim)
    v = v.reshape(batch, seq, n_kv_heads, head_dim)
    positions = jax.lax.broadcasted_iota(jnp.int32, (batch, seq), 1)
    q = L.apply_rope(q, positions, rope_theta)
    k = L.apply_rope(k, positions, rope_theta)
    qg = q.reshape(batch, seq, n_kv_heads, g, head_dim)
    o = _dense_attention(qg, k, v, causal=True)
    return o.reshape(batch * seq, nq)


def _cp_wanted(attn_cp: str, n_heads: int) -> bool:
    """Context-parallel attention: 'auto' turns it on exactly when the head
    count cannot take the model mesh axis (24/28/40 heads vs 16) - there
    head-TP is impossible and GSPMD would replicate attention compute."""
    from repro.distributed import sharding as shd

    mesh = shd.get_mesh()
    if attn_cp == "off" or mesh is None or "model" not in mesh.axis_names:
        return False
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    if attn_cp == "cp":
        return True
    return n_heads % n_model != 0


def attention_apply(params, x, *, positions, acfg: AnalogConfig, n_heads,
                    n_kv_heads, head_dim, rope_theta, mrope=False,
                    cache=None, window=None, flash_threshold=2048,
                    attn_cp="auto", key=None):
    """Returns (out, new_cache).  ``cache``: dict(k, v, len) for decode."""
    b, s, _ = x.shape
    g = n_heads // n_kv_heads
    ks = jax.random.split(key, 4) if key is not None else (None,) * 4
    qkv_lp = None
    if acfg.mode != "digital":
        # the compiled QKV dispatch group (repro.api GroupSpec
        # "column_concat"): canonical storage is the parent node's
        # "_groups" entry, resolved by kind + exact members (any group
        # name works; a group of another kind is never mistaken for the
        # shared-input fusion); "_qkv_plan" is the legacy alias (same
        # fused LayerPlan object) kept for trees lowered by older code
        from repro.exec.plan import find_group

        gp = find_group(params.get("_groups"), "column_concat",
                        ("wq", "wk", "wv"))
        qkv_lp = gp.fused if gp is not None else params.get("_qkv_plan")
    if qkv_lp is not None and (
        qkv_lp.signed_input != acfg.signed_input
        or qkv_lp.chunk_rows != acfg.chunk_rows
        # under static calibration a fused plan is only valid when it was
        # snapshot-calibrated as a group: one shared input LSB
        # (a_scale_in) encodes AND dequantizes the group.  A dynamically-
        # fused plan (one baked a_scale, wq's) would quantize k/v with
        # the wrong static LSB.
        or (acfg.act_calib != "dynamic" and qkv_lp.a_scale_in is None)
    ):
        qkv_lp = None        # baked attrs disagree with this call site
    if qkv_lp is not None:
        # whole-block plan (repro.api): the three same-input projections
        # were fused into ONE dispatch group at compile time - one analog
        # pass over concatenated output columns instead of three
        from repro.exec.run import run_layer

        qkv = run_layer(qkv_lp, x, acfg, key=ks[0])
        nq = n_heads * head_dim
        nkv = n_kv_heads * head_dim
        q, k, v = jnp.split(qkv, [nq, nq + nkv], axis=-1)
    else:
        q = L.linear_apply(params["wq"], x, acfg, key=ks[0])
        k = L.linear_apply(params["wk"], x, acfg, key=ks[1])
        v = L.linear_apply(params["wv"], x, acfg, key=ks[2])
    q = q.reshape(b, s, n_heads, head_dim)
    k = k.reshape(b, s, n_kv_heads, head_dim)
    v = v.reshape(b, s, n_kv_heads, head_dim)
    if "q_norm" in params:
        q = L.norm_apply(params["q_norm"], q)
        k = L.norm_apply(params["k_norm"], k)
    rope = L.apply_mrope if mrope else L.apply_rope
    q = rope(q, positions, rope_theta)
    k = rope(k, positions, rope_theta)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)
    qg = q.reshape(b, s, n_kv_heads, g, head_dim)

    # analog modes: the glue between the QKV and output projections
    # (scores, mixing) contracts at full precision.  A TPU rounds fp32
    # operands to bf16 by default, and the output projection's 5-bit
    # re-quantization amplifies that: on a TPU v5e it moved a 4-layer
    # stablelm-3b-width model's prefill logits 0.298 (relative L2) from
    # the fp32 forward; an un-quantised float forward is 0.357 away.
    glue_precision = (contextlib.nullcontext() if acfg.mode == "digital"
                      else jax.default_matmul_precision("highest"))
    with glue_precision:
        if cache is not None:
            # decode: append to the cache, attend over the valid prefix
            length = cache["len"]                      # scalar int32
            quantized = cache["k"].dtype == jnp.int8
            new_cache = {"len": length + s}
            if quantized:
                # int8 KV cache ("store at ADC resolution", beyond-paper):
                # per-(position, head) symmetric scales; halves the decode
                # memory-roofline term vs bf16 at <1% logit error
                ks_new = jnp.abs(k).max(axis=-1).astype(jnp.float32) / 127.0
                vs_new = jnp.abs(v).max(axis=-1).astype(jnp.float32) / 127.0
                ks_new = jnp.maximum(ks_new, 1e-9)
                vs_new = jnp.maximum(vs_new, 1e-9)
                kq = jnp.clip(jnp.round(k / ks_new[..., None]), -127, 127)
                vq = jnp.clip(jnp.round(v / vs_new[..., None]), -127, 127)
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], kq.astype(jnp.int8), (0, length, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], vq.astype(jnp.int8), (0, length, 0, 0))
                cks = jax.lax.dynamic_update_slice(
                    cache["k_scale"], ks_new, (0, length, 0))
                cvs = jax.lax.dynamic_update_slice(
                    cache["v_scale"], vs_new, (0, length, 0))
                ck_f = ck.astype(jnp.float32) * cks[..., None]
                cv_f = cv.astype(jnp.float32) * cvs[..., None]
                new_cache.update(k_scale=cks, v_scale=cvs)
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, length, 0, 0)
                )
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, length, 0, 0)
                )
                ck_f, cv_f = ck.astype(jnp.float32), cv.astype(jnp.float32)
            if s > flash_threshold:
                # a long prompt into the cache: blockwise, as without one;
                # causality masks every slot past the prompt's end
                o = flash_attention(qg.astype(jnp.float32), ck_f, cv_f,
                                    causal=True, q_offset=length,
                                    window=window)
            else:
                smax = ck.shape[1]
                kpos = jnp.arange(smax)
                qpos = length + jnp.arange(s)
                mask = qpos[:, None] >= kpos[None, :]
                mask &= (kpos < length + s)[None, :]
                if window is not None:
                    mask &= (qpos[:, None] - kpos[None, :]) < window
                sc = jnp.einsum(
                    "bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), ck_f
                ) / jnp.sqrt(head_dim)
                sc = jnp.where(mask[None, None, None], sc, NEG_INF)
                p = L.softmax(sc)
                o = jnp.einsum("bhgqk,bkhd->bqhgd", p, cv_f)
            o = o.astype(x.dtype)
            new_cache.update(k=ck, v=cv)
        else:
            if _cp_wanted(attn_cp, n_heads):
                o = flash_attention_cp(qg, k, v, causal=True, window=window)
            elif s <= flash_threshold:
                o = _dense_attention(qg, k, v, causal=True, window=window)
            else:
                o = flash_attention(qg, k, v, causal=True, window=window)
            new_cache = None

    o = o.reshape(b, s, n_heads * head_dim)
    out = L.linear_apply(params["wo"], o, acfg, key=ks[3])
    return out, new_cache


def init_cache(batch, max_len, n_kv_heads, head_dim, dtype=jnp.bfloat16):
    c = {
        "k": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "len": jnp.zeros((), jnp.int32),
    }
    if dtype == jnp.int8:
        c["k_scale"] = jnp.zeros((batch, max_len, n_kv_heads), jnp.float32)
        c["v_scale"] = jnp.zeros((batch, max_len, n_kv_heads), jnp.float32)
    return c


def cache_specs(dtype=jnp.bfloat16):
    c = {
        "k": ("batch", "kv_seq", "kv_heads", None),
        "v": ("batch", "kv_seq", "kv_heads", None),
        "len": (),
    }
    if dtype == jnp.int8:
        c["k_scale"] = ("batch", "kv_seq", "kv_heads")
        c["v_scale"] = ("batch", "kv_seq", "kv_heads")
    return c
