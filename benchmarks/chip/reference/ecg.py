"""Plain reference of configurations of kind ``ecg``: the paper's
pre-processing (Fig. 7) and the analog classifier (Fig. 4, Fig. 6) in
straightforward ``jax.numpy``, written from the paper and the
configuration, importing nothing of the program.

Per window of raw 12-bit samples ``[channels, samples]``:

- pre-processing: discrete derivative, max minus min over each run of
  ``pool`` samples, right shift by ``quant_shift``, clip to 5-bit codes;
- conv: the codes as ``positions`` rows of ``taps x channels`` inputs
  (tap-major, channel-minor), one analog layer, its 8 outputs per
  position flattened position-major;
- each analog layer (BSS-2 semantics): weight codes
  ``clip(round(w / w_scale), -63, 63)`` times the chip's per-synapse
  gain; per 128-row chunk ``v = gain * (codes . weights) + offset``, an
  8-bit saturating ADC ``clip(round(v), -128, 127)``, the chunks summed;
- between layers: ReLU and a right shift that maps the summed ADC range
  of the layer's chunks onto 5-bit codes;
- the last layer dequantized by ``w_scale / gain`` per column, then the
  mean of each class's copies.

``precision="highest"`` states the configuration's fp32 arithmetic;
``"bfloat16"`` rounds every matmul operand to bfloat16, the control that
a check must fail.
"""
from __future__ import annotations

import numpy as np

ADC_MIN, ADC_MAX, A_MAX, W_MAX = -128.0, 127.0, 31.0, 63.0


def preprocess(raw, cfg: dict):
    import jax.numpy as jnp

    d = jnp.diff(raw, axis=-1)
    t = (d.shape[-1] // cfg["pool"]) * cfg["pool"]
    d = d[..., :t].reshape(d.shape[:-1] + (t // cfg["pool"], cfg["pool"]))
    pooled = d.max(-1) - d.min(-1)
    return jnp.clip(jnp.floor(pooled / (1 << cfg["quant_shift"])), 0, A_MAX)


def shift_for(n_chunks: int) -> int:
    """Smallest right shift that maps ``[0, n_chunks * 127]`` into
    ``[0, 31]``."""
    s = 0
    while (n_chunks * int(ADC_MAX)) >> s > int(A_MAX):
        s += 1
    return s


def analog_layer(codes, layer: dict, *, rows: int, precision: str):
    """Summed ADC codes of one analog layer for unsigned input codes
    ``[..., K]``."""
    import jax
    import jax.numpy as jnp

    w = layer["w"]
    k, n = w.shape
    c = -(-k // rows)
    # the chip's fixed pattern, made by the benchmark from the seed
    chip = layer["fpn"]  # verify: allow-fpn-access
    w_code = jnp.clip(jnp.round(w / layer["w_scale"]), -W_MAX, W_MAX)
    w_eff = w_code * chip["gain"]
    pad = c * rows - k
    w_eff = jnp.pad(w_eff, ((0, pad), (0, 0)))
    codes = jnp.pad(codes, [(0, 0)] * (codes.ndim - 1) + [(0, pad)])
    a = codes.reshape(codes.shape[:-1] + (c, rows))
    wc = w_eff.reshape(c, rows, n)
    if precision == "bfloat16":
        a, wc = a.astype(jnp.bfloat16), wc.astype(jnp.bfloat16)
        prec = jax.lax.Precision.DEFAULT
    else:
        prec = jax.lax.Precision.HIGHEST
    v = jnp.einsum("...ck,ckn->...cn", a, wc, precision=prec,
                   preferred_element_type=jnp.float32)
    v = v * layer["gain"] + chip["chunk_offset"]
    return jnp.clip(jnp.round(v), ADC_MIN, ADC_MAX).sum(-2)


def forward(weights: dict, raw, cfg: dict, precision: str = "highest"):
    """Logits ``[B, classes]`` of raw windows ``[B, channels, samples]``."""
    import jax.numpy as jnp

    rows = cfg["analog"]["chunk_rows"]
    x = preprocess(raw, cfg)                            # [B, C, T]
    b = x.shape[0]
    taps, stride = cfg["conv_taps"], cfg["conv_stride"]
    npos = (x.shape[-1] - taps) // stride + 1
    idx = np.arange(npos)[:, None] * stride + np.arange(taps)[None, :]
    cols = x[:, :, idx].transpose(0, 2, 3, 1).reshape(b, npos, -1)
    h = cols
    for name in ("conv", "fc1"):
        k = weights[name]["w"].shape[0]
        y = analog_layer(h, weights[name], rows=rows, precision=precision)
        h = jnp.clip(jnp.floor(jnp.maximum(y, 0.0)
                               / (1 << shift_for(-(-k // rows)))), 0, A_MAX)
        h = h.reshape(b, -1)
    fc2 = weights["fc2"]
    y = analog_layer(h, fc2, rows=rows, precision=precision)
    y = y * (1.0 * fc2["w_scale"].reshape(-1) / fc2["gain"])
    return y.reshape(b, cfg["classes"], cfg["class_copies"]).mean(-1)


def logit_unit(weights: dict, cfg: dict) -> np.ndarray:
    """Per class, the least change of a logit that one ADC code of one
    copy makes: ``min(w_scale / gain) / copies``."""
    fc2 = weights["fc2"]
    lsb = np.asarray(fc2["w_scale"], np.float64).reshape(-1) / float(
        fc2["gain"])
    lsb = lsb.reshape(cfg["classes"], cfg["class_copies"])
    return lsb.min(-1) / cfg["class_copies"]


def pool_logits(weights: dict, pool: np.ndarray, cfg: dict,
                precision: str = "highest", block: int = 256) -> np.ndarray:
    """The reference's logits for every window of the pool, in blocks."""
    import jax

    fwd = jax.jit(lambda r: forward(weights, r, cfg, precision))
    out = []
    for i in range(0, len(pool), block):
        out.append(np.asarray(fwd(pool[i:i + block]), np.float64))
    return np.concatenate(out)


def compare(answers, ref: np.ndarray, unit: np.ndarray) -> dict:
    """The number compared: over every answer of the window, the widest
    gap of a logit from the reference's, in units of one code of one class
    copy (any fault in the pre-processing or in one of the three layers
    moves some window's logits by whole codes)."""
    worst = 0.0
    for idx, logits in answers:
        want = ref[idx]
        got = np.asarray(logits, np.float64)
        if not np.isfinite(got).all() or got.shape != want.shape:
            return {"max_logit_gap_codes": float("inf")}
        worst = max(worst, float((np.abs(got - want) / unit).max()))
    return {"max_logit_gap_codes": worst}


def check(system, cfg: dict, traffic: dict, rng) -> dict:
    """Compare every answer the window produced with the reference, after
    the program's state is freed."""
    ref = pool_logits(system.weights, system.pool, cfg, "highest")
    return compare(system.answers, ref, logit_unit(system.weights, cfg))


def control(system, cfg: dict, traffic: dict, rng) -> dict:
    """The control: the reference in bfloat16 put in the program's place,
    judged by the same comparison."""
    ref = pool_logits(system.weights, system.pool, cfg, "highest")
    low = pool_logits(system.weights, system.pool, cfg, "bfloat16")
    answers = [(np.arange(len(low)), low)]
    return compare(answers, ref, logit_unit(system.weights, cfg))
