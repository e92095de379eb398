"""RWKV-6 "Finch" block (arXiv:2404.05892): data-dependent-decay linear
attention + squared-ReLU channel mix.

Analog mapping (DESIGN.md §5.1): the R/K/V/G/O and channel-mix projections
are analog tile matmuls; the WKV recurrence is stateful elementwise dynamics
(the BSS-2 *neuron* mode, not the multiplexable VMM mode) and stays digital.

The recurrence here is the O(T) sequential scan - the paper-faithful
baseline.  A chunkwise-parallel formulation is a §Perf hillclimb option.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.analog import AnalogConfig
from repro.core.noise import NoiseConfig
from repro.distributed.sharding import constrain
from repro.models import layers as L

LORA_RANK = 64


def rwkv_init(key, d_model, n_heads, *, d_ff=None,
              noise: NoiseConfig = NoiseConfig(), dtype=jnp.float32):
    head_dim = d_model // n_heads
    d_ff = d_ff or int(3.5 * d_model)
    ks = jax.random.split(key, 12)
    small = lambda k, shape, s=0.01: (jax.random.normal(k, shape) * s).astype(
        jnp.float32
    )
    return {
        "tm": {  # time-mix interpolation factors (token shift)
            "mu_r": small(ks[0], (d_model,)),
            "mu_k": small(ks[1], (d_model,)),
            "mu_v": small(ks[2], (d_model,)),
            "mu_g": small(ks[3], (d_model,)),
            "mu_w": small(ks[4], (d_model,)),
        },
        "wr": L.linear_init(ks[5], d_model, d_model, noise=noise, dtype=dtype),
        "wk": L.linear_init(ks[6], d_model, d_model, noise=noise, dtype=dtype),
        "wv": L.linear_init(ks[7], d_model, d_model, noise=noise, dtype=dtype),
        "wg": L.linear_init(ks[8], d_model, d_model, noise=noise, dtype=dtype),
        "wo": L.linear_init(ks[9], d_model, d_model, noise=noise, dtype=dtype),
        # data-dependent decay: w_t = exp(-exp(w0 + lora(x)))
        "w0": jnp.full((n_heads, head_dim), -2.0, jnp.float32),
        "w_lora_a": small(ks[10], (d_model, LORA_RANK), 0.02),
        "w_lora_b": small(ks[11], (LORA_RANK, d_model), 0.02),
        # per-(head, channel) current-token bonus
        "u": jnp.zeros((n_heads, head_dim), jnp.float32),
    }


def rwkv_specs(noise: NoiseConfig = NoiseConfig()):
    return {
        "tm": {k: (None,) for k in ("mu_r", "mu_k", "mu_v", "mu_g", "mu_w")},
        "wr": L.linear_specs("embed", "heads", noise=noise),
        "wk": L.linear_specs("embed", "heads", noise=noise),
        "wv": L.linear_specs("embed", "heads", noise=noise),
        "wg": L.linear_specs("embed", "heads", noise=noise),
        "wo": L.linear_specs("heads", "embed", noise=noise),
        "w0": ("heads", None),
        "w_lora_a": (None, None),
        "w_lora_b": (None, "heads"),
        "u": ("heads", None),
    }


def rwkv_module_spec(d_model, n_heads, *,
                     noise: NoiseConfig = NoiseConfig()):
    """Declare one RWKV-6 time-mix block for the api front door:
    ``api.compile(rwkv_module_spec(d, h), params, run)`` bakes the five
    projections once - r/k/v/g fused into ONE ``batch_concat`` dispatch
    group (the four token-shift mixes stream through one array config,
    4 -> 1 analog dispatches; paper §II-D array filling) - and
    ``CompiledModel.apply(x, cache=, key=)`` is :func:`rwkv_apply` over
    the pre-lowered tree.  ``params`` is :func:`rwkv_init`'s dict."""
    from repro import api

    def _apply(model, x, *, cache=None, key=None):
        return rwkv_apply(model.lower(), x, acfg=model.acfg,
                          n_heads=n_heads, cache=cache, key=key)

    names = ("wr", "wk", "wv", "wg")
    return api.ModuleSpec(
        name=f"rwkv_tmix_{d_model}x{n_heads}",
        kind="tree",
        apply_fn=_apply,
        layers=tuple(
            [api.LayerSpec(n, d_model, d_model, group="rkvg")
             for n in names]
            + [api.LayerSpec("wo", d_model, d_model)]
        ),
        groups=(api.GroupSpec("rkvg", "batch_concat", names),),
        param_axes=rwkv_specs(noise),
    )


def _token_shift(x, x_prev):
    """shift sequence right by one; x_prev is the carry for step 0."""
    return jnp.concatenate([x_prev[:, None], x[:, :-1]], axis=1)


def _lerp(x, x_shift, mu):
    return x + (x_shift - x) * mu


def wkv_scan(r, k, v, w, u, state0):
    """Sequential WKV-6 recurrence.

    r,k,v: [B, T, H, D]; w: [B, T, H, D] decay in (0,1);
    u: [H, D]; state0: [B, H, D, D] -> (out [B,T,H,D], state [B,H,D,D])
    """

    def step(state, inp):
        r_t, k_t, v_t, w_t = inp                       # [B, H, D] each
        kv = k_t[..., :, None] * v_t[..., None, :]     # [B, H, D, D]
        y = jnp.einsum(
            "bhi,bhij->bhj", r_t, state + u[None, :, :, None] * kv
        )
        state = w_t[..., :, None] * state + kv
        return state, y

    rs, ks_, vs, ws = (jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    state, ys = jax.lax.scan(step, state0, (rs, ks_, vs, ws))
    return jnp.moveaxis(ys, 0, 1), state


def rwkv_apply(params, x, *, acfg: AnalogConfig, n_heads, cache=None,
               key=None):
    """x: [B, T, d].  cache: {"x_prev": [B, d], "state": [B, H, D, D]} for
    decode; None for train/prefill (zero initial state)."""
    b, t, d = x.shape
    hd = d // n_heads
    x_prev = cache["x_prev"] if cache is not None else jnp.zeros_like(x[:, 0])
    xs = _token_shift(x, x_prev)
    tm = params["tm"]
    xr = _lerp(x, xs, tm["mu_r"])
    xk = _lerp(x, xs, tm["mu_k"])
    xv = _lerp(x, xs, tm["mu_v"])
    xg = _lerp(x, xs, tm["mu_g"])
    xw = _lerp(x, xs, tm["mu_w"])

    kk = jax.random.split(key, 5) if key is not None else (None,) * 5
    gp = None
    if acfg.mode != "digital":
        # resolved by kind + exact members, not by group name: only a
        # batch_concat plan over these four projections takes this path
        from repro.exec.plan import find_group

        gp = find_group(params.get("_groups"), "batch_concat",
                        ("wr", "wk", "wv", "wg"))
    if gp is not None and (
        gp.fused.signed_input != acfg.signed_input
        or gp.fused.chunk_rows != acfg.chunk_rows
    ):
        gp = None        # baked attrs disagree with this call site
    if gp is not None:
        # compiled r/k/v/g dispatch group (repro.api GroupSpec
        # "batch_concat"): the four same-geometry projections replay as
        # ONE analog dispatch - member matrices on disjoint column blocks
        # of one array config, all four token-shift mixes streamed
        # through in the same pass; each member keeps its own input
        # encoding, so the result is bit-exact vs the four solo
        # dispatches (under dynamic AND static activation calibration)
        from repro.exec.run import run_batch_concat

        r, k, v, g = run_batch_concat(gp, (xr, xk, xv, xg), acfg,
                                      key=kk[0])
    else:
        r = L.linear_apply(params["wr"], xr, acfg, key=kk[0])
        k = L.linear_apply(params["wk"], xk, acfg, key=kk[1])
        v = L.linear_apply(params["wv"], xv, acfg, key=kk[2])
        g = L.linear_apply(params["wg"], xg, acfg, key=kk[3])

    dd = jnp.tanh(xw.astype(jnp.float32) @ params["w_lora_a"]) @ params[
        "w_lora_b"
    ]
    w_log = params["w0"].reshape(1, 1, d) + dd.reshape(b, t, d)
    w = jnp.exp(-jnp.exp(w_log))                       # decay in (0, 1)

    shape = (b, t, n_heads, hd)
    r, k, v, w = (a.astype(jnp.float32).reshape(shape) for a in (r, k, v, w))
    r = constrain(r, "batch", "seq", "heads", None)
    state0 = (
        cache["state"]
        if cache is not None
        else jnp.zeros((b, n_heads, hd, hd), jnp.float32)
    )
    y, state = wkv_scan(r, k, v, w, params["u"], state0)
    y = y.reshape(b, t, d)
    # group norm over heads, then output gate + projection
    yh = y.reshape(b, t, n_heads, hd)
    yh = yh * jax.lax.rsqrt(L.ordered_sum(yh * yh) / hd + 1e-5)
    y = (yh.reshape(b, t, d) * jax.nn.silu(g.astype(jnp.float32))).astype(
        x.dtype
    )
    out = L.linear_apply(params["wo"], y, acfg, key=kk[4])
    new_cache = {"x_prev": x[:, -1], "state": state}
    return out, new_cache


def rwkv_cache_specs():
    return {"x_prev": ("batch", None), "state": ("batch", "heads", None, None)}


# ------------------------------------------------------- channel mix (FFN)
def channel_mix_init(key, d_model, d_ff, *,
                     noise: NoiseConfig = NoiseConfig(), dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {
        "mu_k": jnp.zeros((d_model,), jnp.float32),
        "wk": L.linear_init(ks[0], d_model, d_ff, noise=noise, dtype=dtype),
        "wv": L.linear_init(ks[1], d_ff, d_model, noise=noise, dtype=dtype),
    }


def channel_mix_specs(noise: NoiseConfig = NoiseConfig()):
    return {
        "mu_k": (None,),
        "wk": L.linear_specs("embed", "mlp", noise=noise),
        "wv": L.linear_specs("mlp", "embed", noise=noise),
    }


def channel_mix_apply(params, x, *, acfg: AnalogConfig, cache=None, key=None):
    b, t, d = x.shape
    x_prev = cache["x_prev"] if cache is not None else jnp.zeros_like(x[:, 0])
    xs = _token_shift(x, x_prev)
    xk = _lerp(x, xs, params["mu_k"])
    kk = jax.random.split(key, 2) if key is not None else (None, None)
    h = L.linear_apply(params["wk"], xk, acfg, key=kk[0])
    h = jnp.square(jax.nn.relu(h))
    h = constrain(h, "batch", "seq", "mlp")
    y = L.linear_apply(params["wv"], h, acfg, key=kk[1])
    return y, {"x_prev": x[:, -1]}
