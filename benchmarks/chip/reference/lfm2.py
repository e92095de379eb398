"""Plain reference of configurations of kind ``lfm2``: LFM2-8B-A1B's
first layers, one chip's share of its experts, every parameter matmul on
the emulated BSS-2 analog chip, in straightforward ``jax.numpy``, written
from the configuration and the published equations, importing nothing of
the program.  The analog layer is ``reference/lm.py``'s ``analog()``.

Model (LiquidAI LFM2, ``config.json`` of LiquidAI/LFM2-8B-A1B):

- each layer ``h = x + op(RMSNorm(x))``, then ``x' = h + ff(RMSNorm(h))``,
  RMSNorm ``x * rsqrt(mean(x * x) + eps) * scale``;
- conv ``op`` (``layer_types`` "conv"): ``[b, c, v~] = split3(W_in u)``
  (``W_in`` d -> 3d, no bias), ``v = b * v~``, the causal depthwise
  convolution of ``conv_L_cache`` taps ``z_t = sum_j k_j * v_{t-2+j}``
  (no bias; the taps summed in order), ``y = W_out (c * z)``
  (``W_out`` d -> d).  Its state between calls is the last two ``v``;
- attention ``op`` ("full_attention"): grouped-query attention, 32 query
  and 8 key/value heads of 64; q and k each take an RMSNorm over the head
  dim (learned scale), then rotary embedding over the whole head (GPT-NeoX
  halves, ``rope_theta``), causal softmax, ``W_o``;
- dense ``ff`` (the first ``num_dense_layers``): ``W_down(silu(W_gate u) *
  W_up u)``;
- MoE ``ff``: ``s = sigmoid(W_r u)`` over all ``num_experts_published``
  experts (fp32, the router's full width); the top ``num_experts_per_tok``
  of ``s + expert_bias`` chosen; weights ``g = s_sel / (sum s_sel +
  1e-6)`` (``norm_topk_prob``; ``routed_scaling_factor`` 1); the output
  ``sum over the chosen experts this chip holds of g_e FFN_e(u)``, summed
  in selection order, each ``FFN_e`` a SwiGLU of width
  ``moe_intermediate_size``;
- a final RMSNorm and the head (its master weight is the embedding table,
  transposed).

Departures from the published model, shared with the program: the
experts this chip does not hold add nothing (one chip's share of an
expert-parallel layer); the head is an analog layer of its own holding
the tied matrix; every analog layer's input LSB is static (``a_scale``),
set by :func:`calibrate` from a calibration batch.

Analog layers: ``W_in``, ``W_out``, q/k/v/o, every FFN matrix and the
head.  Digital fp32 at ``HIGHEST``: the router, the convolution, the
norms and the attention softmax.

Every sum of the glue (the norms' mean squares, the softmax's
denominator, the routing weights' total) is taken in one stated order,
pairwise by halves (:func:`ordered_sum`), the order the program states
for every glue sum of its models.  A ``reduce`` would leave the order to
the compiler, which picks it per fusion: on a TPU the program and this
reference then part in the last bit of a few norms, a 5-bit input
quantizer turns that bit into a code, and the one attention layer
spreads the code's effect over every later position of the row.

A served batch is replayed as it was served: the prompts as one call,
then one call per generated token with the token the system served
(teacher forcing).  The gap of a served token is how far its logit lies
below the reference's best at that position.  The replay also recounts
the (token, held expert) pairs that the batch routed, with the routing
decisions whose 4th and 5th biased scores lie within ``NEAR_TIE`` counted
as either way.

``precision="highest"`` states the configuration's fp32 arithmetic;
``"bfloat16"`` rounds every matmul operand to bfloat16, the control.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib

import numpy as np

NEAR_TIE = 1e-5


def _load_lm():
    path = pathlib.Path(__file__).with_name("lm.py")
    spec = importlib.util.spec_from_file_location("lfm2_reference_lm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


LM = _load_lm()
A_MAX = LM.A_MAX


def kinds(cfg: dict) -> list:
    """(mixer, ff) of each of the configuration's layers."""
    return [("attn" if t == "full_attention" else "conv",
             "mlp" if i < cfg["num_dense_layers"] else "moe")
            for i, t in enumerate(
                cfg["layer_types"][:cfg["num_hidden_layers"]])]


def ordered_sum(x):
    """Sum over the last axis, keeping it, in one fixed order: the two
    halves added elementwise, an odd last element carried, until one is
    left.  The order is stated, so that a compiler cannot pick another
    per fusion (see the module doc)."""
    import jax.numpy as jnp

    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        head = x[..., :h] + x[..., h:2 * h]
        x = head if x.shape[-1] == 2 * h else jnp.concatenate(
            [head, x[..., 2 * h:]], axis=-1)
    return x


def rms_norm(x, p, eps: float):
    import jax

    ms = ordered_sum(x * x) / x.shape[-1]
    return x * jax.lax.rsqrt(ms + eps) * p["scale"]


def softmax(x):
    import jax
    import jax.numpy as jnp

    e = jnp.exp(x - jax.lax.stop_gradient(x.max(-1, keepdims=True)))
    return e / ordered_sum(e)


def _lsb(x):
    import jax.numpy as jnp

    return jnp.maximum(jnp.abs(x).max() + 1e-9, 1e-8) / A_MAX


class Model:
    """The forward of one call at one precision.  ``calib=True`` runs
    every analog layer at its own call's abs-max LSB and records it (the
    calibration pass); otherwise every layer runs at its ``a_scale``."""

    def __init__(self, cfg: dict, precision: str, calib: bool = False):
        self.cfg, self.precision, self.calib = cfg, precision, calib
        self.lsbs = {}

    def lin(self, path: str, x, p):
        lsb = _lsb(x) if self.calib else p["a_scale"]
        if self.calib:
            self.lsbs[path] = lsb
        return LM.analog(x, p, rows=self.cfg["analog"]["chunk_rows"],
                         precision=self.precision, lsb=lsb)

    def dot(self, a, b, spec):
        return LM._dot(a, b, spec, self.precision)

    def conv(self, path, p, u, state):
        import jax.numpy as jnp

        b, s, d = u.shape
        bcx = self.lin(path + ".in_proj", u.reshape(b * s, d),
                       p["in_proj"]).reshape(b, s, 3 * d)
        bg, cg, xv = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
        k = p["conv_w"]
        ext = jnp.concatenate([state, bg * xv], axis=1)
        z = k[0] * ext[:, 0:s]
        for j in range(1, k.shape[0]):
            z = z + k[j] * ext[:, j:j + s]
        y = self.lin(path + ".out_proj", (cg * z).reshape(b * s, d),
                     p["out_proj"])
        return y.reshape(b, s, d), ext[:, s:]

    def attn(self, path, p, u, kv, start):
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        eps = cfg["norm_eps"]
        nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        b, s, d = u.shape
        dh = d // nh
        h = u.reshape(b * s, d)
        q = self.lin(path + ".wq", h, p["wq"]).reshape(b, s, nh, dh)
        k = self.lin(path + ".wk", h, p["wk"]).reshape(b, s, nkv, dh)
        v = self.lin(path + ".wv", h, p["wv"]).reshape(b, s, nkv, dh)
        q = rms_norm(q, p["q_norm"], eps)
        k = rms_norm(k, p["k_norm"], eps)
        positions = start + jnp.arange(s)
        q = LM.rope(q, positions, cfg["rope_theta"])
        k = LM.rope(k, positions, cfg["rope_theta"])
        ck, cv = kv
        ck = jax.lax.dynamic_update_slice(ck, k, (0, start, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v, (0, start, 0, 0))
        qg = q.reshape(b, s, nkv, nh // nkv, dh)
        sc = self.dot(qg, ck, "bqhgd,bkhd->bhgqk") / np.sqrt(dh)
        mask = positions[:, None] >= jnp.arange(ck.shape[1])[None, :]
        sc = jnp.where(mask[None, None, None], sc, -jnp.inf)
        o = self.dot(softmax(sc), cv, "bhgqk,bkhd->bqhgd")
        y = self.lin(path + ".wo", o.reshape(b * s, d), p["wo"])
        return y.reshape(b, s, d), (ck, cv)

    def ffn(self, path, p, u):
        import jax

        up = self.lin(path + ".up", u, p["up"])
        gate = self.lin(path + ".gate", u, p["gate"])
        return self.lin(path + ".down", jax.nn.silu(gate) * up, p["down"])

    def route(self, p, u):
        """(chosen ids [T, k], weights [T, k], biased scores [T, E])."""
        import jax
        import jax.numpy as jnp

        s = jax.nn.sigmoid(self.dot(u, p["router"]["w"], "td,de->te"))
        biased = s + p["expert_bias"]
        _, sel = jax.lax.top_k(biased, self.cfg["num_experts_per_tok"])
        w = jnp.take_along_axis(s, sel, axis=-1)
        return sel, w / (ordered_sum(w) + 1e-6), biased

    def moe(self, path, p, u):
        """Every held expert over every token (rows are independent, so a
        routed row's value is what the expert gives it alone); returns
        the layer's output and its routing record."""
        import jax
        import jax.numpy as jnp

        sel, w, biased = self.route(p, u)
        held = p["held"].astype(jnp.int32)
        match = sel[:, :, None] == held[None, None, :]
        is_held = match.any(-1)
        hidx = jnp.argmax(match, axis=-1)
        outs = []
        for e in range(held.shape[0]):
            pe = jax.tree.map(lambda a: a[e], p["experts"])
            outs.append(self.ffn(f"{path}.experts.{e}", pe, u))
        ye = jnp.stack(outs)                                  # [H, T, d]
        parts = ye[hidx, jnp.arange(u.shape[0])[:, None]]     # [T, k, d]
        y = jnp.zeros(u.shape, jnp.float32)
        for j in range(sel.shape[1]):
            y = y + jnp.where(is_held[:, j, None],
                              w[:, j, None] * parts[:, j], 0.0)
        return y, {"sel": sel, "biased": biased}

    def __call__(self, weights, tokens, state, start):
        """One call of ``tokens [B, S]`` at positions ``start..`` from
        ``state`` (per layer: the conv's last two ``v`` or the attention's
        keys and values).  Returns the last position's logits, the new
        state and each MoE layer's routing."""
        import jax.numpy as jnp

        cfg = self.cfg
        eps = cfg["norm_eps"]
        b, s = tokens.shape
        d = cfg["hidden_size"]
        x = weights["embed"]["table"][tokens]
        new_state, routing = [], []
        for i, (mixer, ff) in enumerate(kinds(cfg)):
            p = weights["layers"][f"l{i}"]
            path = f"layers.l{i}"
            u = rms_norm(x, p["ln1"], eps)
            if mixer == "conv":
                y, st = self.conv(path + ".conv", p["conv"], u, state[i])
            else:
                y, st = self.attn(path + ".attn", p["attn"], u, state[i],
                                  start)
            new_state.append(st)
            x = x + y
            u = rms_norm(x, p["ln2"], eps).reshape(b * s, d)
            if ff == "mlp":
                y = self.ffn(path + ".mlp", p["mlp"], u)
            else:
                y, r = self.moe(path + ".moe", p["moe"], u)
                routing.append(r)
            x = x + y.reshape(b, s, d)
        h = rms_norm(x, weights["final_norm"], eps)
        if self.calib:
            self.lsbs["lm_head"] = _lsb(h)
            return None, new_state, routing
        logits = self.lin("lm_head", h[:, -1], weights["lm_head"])
        return logits, new_state, routing


def init_state(cfg: dict, b: int, t: int) -> list:
    import jax.numpy as jnp

    d = cfg["hidden_size"]
    dh = d // cfg["num_attention_heads"]
    kv = (b, t, cfg["num_key_value_heads"], dh)
    return [jnp.zeros((b, cfg["conv_L_cache"] - 1, d), jnp.float32)
            if m == "conv" else (jnp.zeros(kv, jnp.float32),
                                 jnp.zeros(kv, jnp.float32))
            for m, _ in kinds(cfg)]


def calibrate(weights, cfg: dict, tokens: np.ndarray):
    """``weights`` with every analog layer's static input LSB set: the
    abs-max of its input over one fp32 call of ``tokens [B, S]`` over 31
    (each held expert over every token of the call).  The program and the
    reference both run at these LSBs."""
    import jax
    import jax.numpy as jnp

    def run(w, t):
        m = Model(cfg, "highest", calib=True)
        m(w, t, init_state(cfg, *t.shape), 0)
        return m.lsbs

    lsbs = jax.jit(run)(weights, jnp.asarray(tokens))

    def put(node, path):
        if isinstance(node, dict) and "w" in node and "a_scale" in node:
            return {**node, "a_scale": lsbs[path]}
        if isinstance(node, dict):
            if path.endswith(".experts"):
                h = node["up"]["w"].shape[0]
                return {m: {**node[m], "a_scale": jnp.stack(
                    [lsbs[f"{path}.{e}.{m}"] for e in range(h)])}
                    for m in node}
            return {k: put(v, f"{path}.{k}" if path else k)
                    for k, v in node.items()}
        return node

    return put(weights, "")


class Replay:
    """Jitted prefill and decode calls of the reference at one precision."""

    def __init__(self, weights, cfg: dict, precision: str):
        import jax

        self.weights, self.cfg = weights, cfg

        def call(w, t, st, start):
            return Model(cfg, precision)(w, t, st, start)

        self.prefill = jax.jit(functools.partial(call, start=0))
        self.decode = jax.jit(call)

    def run(self, prompts: np.ndarray, served: np.ndarray):
        """The logits ``[B, vocab]`` at each served position and the
        routing of every call, for equal-length ``prompts [B, P]`` and
        served tokens ``[B, T]``."""
        import jax.numpy as jnp

        b, p = prompts.shape
        t = served.shape[1]
        state = init_state(self.cfg, b, p + t)
        logits, state, routing = self.prefill(self.weights,
                                              jnp.asarray(prompts), state)
        out, routes = [logits], [routing]
        for i in range(t - 1):
            logits, state, routing = self.decode(
                self.weights, jnp.asarray(served[:, i:i + 1]), state,
                jnp.asarray(p + i, jnp.int32))
            out.append(logits)
            routes.append(routing)
        return out, routes


def held_pairs(routes: list, held: np.ndarray, k: int) -> dict:
    """The (token, held expert) pairs of every call's routing, and how far
    the near-ties could move the count: a decision whose k-th and
    (k+1)-th biased scores lie within ``NEAR_TIE`` may go either way."""
    count = up = down = ties = 0
    held = set(np.asarray(held).tolist())
    for routing in routes:
        for r in routing:
            sel = np.asarray(r["sel"])
            biased = np.asarray(r["biased"])
            count += int(np.isin(sel, list(held)).sum())
            order = np.argsort(-biased, axis=-1, kind="stable")
            kth, nxt = order[:, k - 1], order[:, k]
            gap = (np.take_along_axis(biased, kth[:, None], -1)
                   - np.take_along_axis(biased, nxt[:, None], -1))[:, 0]
            near = gap <= NEAR_TIE
            ties += int(near.sum())
            in_k = np.isin(kth, list(held))
            in_n = np.isin(nxt, list(held))
            down += int((near & in_k & ~in_n).sum())
            up += int((near & ~in_k & in_n).sum())
    return {"count": count, "lo": count - down, "hi": count + up,
            "near_ties": ties}


def _stack(batch):
    prompts, outs = batch[0], batch[1]
    if len({len(x) for x in prompts}) != 1 or len({len(o) for o in outs}) != 1:
        raise ValueError("the reference replays batches of equal lengths")
    return np.stack(prompts), np.stack(outs)


def _held(weights):
    for node in weights["layers"].values():
        if "moe" in node:
            return np.asarray(node["moe"]["held"]).astype(np.int32)
    return np.zeros((0,), np.int32)


def served_gaps(ref: Replay, batches: list) -> dict:
    """Over every served token of ``batches``: the widest gap by which its
    reference logit lies below the reference's best at its position; and
    the widest distance of the program's count of (token, held expert)
    pairs (each batch's third entry) from the reference's recount."""
    import jax.numpy as jnp

    worst, tokens, rows_gap, ties = 0.0, 0, 0, 0
    held = _held(ref.weights)
    k = ref.cfg["num_experts_per_tok"]
    for batch in batches:
        prompts, served = _stack(batch)
        if ((served < 0) | (served >= ref.cfg["vocab_size"])).any():
            return {"max_served_gap": float("inf"), "tokens": tokens,
                    "held_rows_gap": float("inf")}
        logits_all, routes = ref.run(prompts, served)
        for i, logits in enumerate(logits_all):
            tok = jnp.asarray(served[:, i])
            gap = logits.max(-1) - jnp.take_along_axis(
                logits, tok[:, None], -1)[:, 0]
            worst = max(worst, float(gap.max()))
            tokens += len(tok)
        pairs = held_pairs(routes, held, k)
        ties += pairs["near_ties"]
        got = batch[2] if len(batch) > 2 else None
        if got is None:
            rows_gap = float("inf")
        else:
            rows_gap = max(rows_gap, pairs["lo"] - got, got - pairs["hi"])
    return {"max_served_gap": worst, "held_rows_gap": float(rows_gap),
            "routing_near_ties": ties, "tokens": tokens}


def sample(batches: list, n: int, rng: np.random.Generator) -> list:
    return LM.sample(batches, n, rng)


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
        for hi, lo in zip(ref.run(prompts, served)[0],
                          low.run(prompts, served)[0]):
            pick = lo.argmax(-1)
            gap = hi.max(-1) - jnp.take_along_axis(hi, pick[:, None],
                                                   -1)[:, 0]
            worst = max(worst, float(gap.max()))
            tokens += len(pick)
    return {"max_served_gap": worst, "tokens": tokens}
