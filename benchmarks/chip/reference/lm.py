"""Plain reference of configurations of kind ``lm``: a decoder LM with
every parameter matmul on the emulated BSS-2 analog chip, in
straightforward ``jax.numpy``, written from the configuration and the
paper's VMM semantics, importing nothing of the program.

Model (stablelm-style): token embedding; per layer LayerNorm, q/k/v
projections, rotary position embedding over the whole head (GPT-NeoX
halves), causal softmax attention, output projection, residual,
LayerNorm, SwiGLU MLP (``silu(gate) * up``, then down), residual; final
LayerNorm and an untied head.

Every projection is one analog layer (Fig. 4 of the paper):

- the input's LSB is its largest magnitude over the whole call (every
  row of the batch, every position of the call) over 31;
- signed inputs take two passes, the positive and the negative part,
  each as 5-bit codes ``clip(round(+-x / lsb), 0, 31)``;
- weight codes ``clip(round(w / w_scale), -63, 63)`` times the chip's
  per-column and per-row gain;
- per 128-row chunk ``v = gain * (codes . weights) + offset`` and an 8-bit
  saturating ADC ``clip(round(v), -128, 127)``; the positive pass's codes
  minus the negative pass's, summed over chunks;
- dequantized by ``lsb * w_scale / gain``.

A served batch is replayed as it was served: the prompts as one call,
then one call per generated token with the token the system served
(teacher forcing), each call's LSBs over the whole batch, keys and
values kept per position.  The gap of a served token is how far its
logit lies below the reference's best at that position.

``precision="highest"`` states the configuration's fp32 arithmetic;
``"bfloat16"`` rounds every matmul operand to bfloat16, the control.
"""
from __future__ import annotations

import functools

import numpy as np

ADC_MIN, ADC_MAX, A_MAX, W_MAX = -128.0, 127.0, 31.0, 63.0


def _dot(a, b, spec, precision):
    import jax
    import jax.numpy as jnp

    if precision == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def analog(x, lin: dict, *, rows: int, precision: str, lsb=None):
    """One analog layer over ``x [M, K]``; ``lsb`` defaults to the call's
    own (largest magnitude of ``x`` over 31)."""
    import jax
    import jax.numpy as jnp

    w = lin["w"]
    k, n = w.shape
    c = -(-k // rows)
    if lsb is None:
        lsb = jnp.maximum(jnp.abs(x).max() + 1e-9, 1e-8) / A_MAX
    # the chip's fixed pattern, made by the benchmark from the seed
    chip = lin["fpn"]  # verify: allow-fpn-access
    w_code = jnp.clip(jnp.round(w / lin["w_scale"]), -W_MAX, W_MAX)
    w_eff = (w_code * chip["col_gain"][None, :]) * chip["row_gain"][:, None]
    pad = c * rows - k
    w_eff = jnp.pad(w_eff, ((0, pad), (0, 0))).reshape(c, rows, n)
    pos = jnp.clip(jnp.round(x / lsb), 0.0, A_MAX)
    neg = jnp.clip(jnp.round(-x / lsb), 0.0, A_MAX)
    a = jnp.stack([pos, neg])                             # [2, M, K]
    a = jnp.pad(a, ((0, 0), (0, 0), (0, pad)))
    a = a.reshape(2, a.shape[1], c, rows).transpose(2, 0, 1, 3)
    gain, off = lin["gain"], chip["chunk_offset"]

    def chunk(acc, inp):
        a_c, w_c, off_c = inp
        v = _dot(a_c, w_c, "smk,kn->smn", precision) * gain + off_c
        adc = jnp.clip(jnp.round(v), ADC_MIN, ADC_MAX)
        return acc + (adc[0] - adc[1]), None

    y, _ = jax.lax.scan(chunk, jnp.zeros((x.shape[0], n), jnp.float32),
                        (a, w_eff, off))
    return y * (lsb * lin["w_scale"].reshape(-1) / gain)


def layer_norm(x, p, eps: float):
    import jax

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def rope(x, positions, theta: float):
    """``x [B, S, H, dh]`` rotated by ``positions [S]`` over the whole
    head: pairs ``(i, i + dh/2)``."""
    import jax.numpy as jnp

    dh = x.shape[-1]
    freqs = 1.0 / theta ** (2.0 * jnp.arange(dh // 2, dtype=jnp.float32)
                            / dh)
    ang = positions.astype(jnp.float32)[:, None] * freqs        # [S, dh/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def step(weights, tokens, cache, start, cfg: dict, precision: str):
    """One call of ``tokens [B, S]`` at positions ``start..start+S-1``
    against ``cache`` (keys and values ``[L, B, T, H, dh]`` of the earlier
    positions).  Returns the last position's logits ``[B, vocab]`` and the
    cache with this call's keys and values written."""
    import jax
    import jax.numpy as jnp

    rows = cfg["analog"]["chunk_rows"]
    eps = cfg["layer_norm_eps"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"]
    dh = d // nh
    b, s = tokens.shape
    t_max = cache[0].shape[2]
    positions = start + jnp.arange(s)
    lin = functools.partial(analog, rows=rows, precision=precision)
    x = weights["embed"]["table"][tokens]                      # [B, S, d]

    def layer(x, inp):
        p, ck, cv = inp
        h = layer_norm(x, p["ln1"], eps).reshape(b * s, d)
        q = lin(h, p["attn"]["wq"]).reshape(b, s, nh, dh)
        k = lin(h, p["attn"]["wk"]).reshape(b, s, nkv, dh)
        v = lin(h, p["attn"]["wv"]).reshape(b, s, nkv, dh)
        q = rope(q, positions, cfg["rope_theta"])
        k = rope(k, positions, cfg["rope_theta"])
        ck = jax.lax.dynamic_update_slice(ck, k, (0, start, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v, (0, start, 0, 0))
        g = nh // nkv
        qg = q.reshape(b, s, nkv, g, dh)
        sc = _dot(qg, ck, "bqhgd,bkhd->bhgqk", precision) / np.sqrt(dh)
        kpos = jnp.arange(t_max)
        mask = positions[:, None] >= kpos[None, :]
        sc = jnp.where(mask[None, None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = _dot(pr, cv, "bhgqk,bkhd->bqhgd", precision)
        o = o.reshape(b * s, nh * dh)
        x = x + lin(o, p["attn"]["wo"]).reshape(b, s, d)
        h = layer_norm(x, p["ln2"], eps).reshape(b * s, d)
        up = lin(h, p["mlp"]["up"])
        gate = lin(h, p["mlp"]["gate"])
        y = lin(jax.nn.silu(gate) * up, p["mlp"]["down"])
        return x + y.reshape(b, s, d), (ck, cv)

    x, (ck, cv) = jax.lax.scan(layer, x, (weights["layers"]["l0"],
                                          cache[0], cache[1]))
    h = layer_norm(x, weights["final_norm"], eps)
    # the head's LSB is over every position of the call; only the last
    # position's logits are needed
    head = weights["lm_head"]
    lsb = jnp.maximum(jnp.abs(h).max() + 1e-9, 1e-8) / A_MAX
    logits = lin(h[:, -1], head, lsb=lsb)
    return logits, (ck, cv)


class Replay:
    """Jitted prefill and decode steps of the reference at one precision."""

    def __init__(self, weights, cfg: dict, precision: str):
        import jax

        self.weights, self.cfg = weights, cfg
        fn = functools.partial(step, cfg=cfg, precision=precision)
        self.prefill = jax.jit(lambda w, t, c: fn(w, t, c, 0))
        self.decode = jax.jit(fn)

    def logits(self, prompts: np.ndarray, served: np.ndarray) -> list:
        """The logits ``[B, vocab]`` at each served position of a batch of
        equal-length prompts ``[B, P]`` and served tokens ``[B, T]``."""
        import jax.numpy as jnp

        cfg = self.cfg
        b, p = prompts.shape
        t = served.shape[1]
        hd = cfg["hidden_size"] // cfg["num_attention_heads"]
        shape = (cfg["num_hidden_layers"], b, p + t,
                 cfg["num_key_value_heads"], hd)
        cache = (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
        out = []
        logits, cache = self.prefill(self.weights, jnp.asarray(prompts),
                                     cache)
        out.append(logits)
        for i in range(t - 1):
            logits, cache = self.decode(
                self.weights, jnp.asarray(served[:, i:i + 1]), cache,
                jnp.asarray(p + i, jnp.int32))
            out.append(logits)
        return out


def _stack(batch):
    prompts, outs = batch
    if len({len(x) for x in prompts}) != 1 or len({len(o) for o in outs}) != 1:
        raise ValueError("the reference replays batches of equal lengths")
    return np.stack(prompts), np.stack(outs)


def sample(batches: list, n: int, rng: np.random.Generator) -> list:
    """``n`` of the window's batches, drawn from the seed."""
    n = min(n, len(batches))
    return [batches[i] for i in sorted(rng.choice(len(batches), n,
                                                  replace=False))]


def served_gaps(ref: Replay, batches: list) -> dict:
    """Over every served token of ``batches``: the widest gap by which its
    reference logit lies below the reference's best at its position."""
    import jax.numpy as jnp

    worst, tokens = 0.0, 0
    for batch in batches:
        prompts, served = _stack(batch)
        vocab = ref.cfg["vocab_size"]
        if ((served < 0) | (served >= vocab)).any():
            return {"max_served_gap": float("inf"), "tokens": tokens}
        for i, logits in enumerate(ref.logits(prompts, served)):
            tok = jnp.asarray(served[:, i])
            gap = logits.max(-1) - jnp.take_along_axis(
                logits, tok[:, None], -1)[:, 0]
            worst = max(worst, float(gap.max()))
            tokens += len(tok)
    return {"max_served_gap": worst, "tokens": tokens}


def check(system, cfg: dict, traffic: dict, rng) -> dict:
    """Replay a sample of the window's batches, drawn from the seed, after
    the program's state is freed."""
    ref = Replay(system.weights, cfg, "highest")
    return served_gaps(ref, sample(system.batches,
                                   traffic["check"]["batches"], rng))


def control(system, cfg: dict, traffic: dict, rng) -> dict:
    """The control: at each position of the same prompts and served
    tokens, the gap of the token the bfloat16 reference puts first."""
    import jax.numpy as jnp

    ref = Replay(system.weights, cfg, "highest")
    low = Replay(system.weights, cfg, "bfloat16")
    worst, tokens = 0.0, 0
    for batch in sample(system.batches, traffic["check"]["batches"], rng):
        prompts, served = _stack(batch)
        for hi, lo in zip(ref.logits(prompts, served),
                          low.logits(prompts, served)):
            pick = lo.argmax(-1)
            gap = hi.max(-1) - jnp.take_along_axis(hi, pick[:, None],
                                                   -1)[:, 0]
            worst = max(worst, float(gap.max()))
            tokens += len(pick)
    return {"max_served_gap": worst, "tokens": tokens}
