"""System driver for configurations of kind ``lfm2``: LFM2's first layers
(gated short-conv and attention token mixers, dense and held-expert
feed-forwards), every parameter matmul on the emulated analog chip,
served by the program's ``ServeEngine(...).serve(requests)``.

A request is a prompt of random token ids and a number of tokens to
generate greedily; a call of the system is one ``serve`` of up to
``batch`` requests (padded prefill, then batched decode steps, each token
read back on the host).

The weights and the chip's fixed pattern are the benchmark's, made from
the seed in one jitted call on the device, in the program's layout; the
head's master weight is the embedding table, transposed.  Every analog
layer's static input LSB is set at set-up by the reference's calibration
pass over a seeded batch, so the program and the reference run at the
same LSBs.  Each call runs under a collector, so that the program counts
its routed (token, held expert) pairs and expert row tiles (read back
once a call, after its last step); the driver keeps each batch's count
for the check and the window's sums for the readers.
"""
from __future__ import annotations

import numpy as np

from chipbench import opcount, opcount_lfm2, spec

STREAM_WEIGHTS, STREAM_REQUESTS, STREAM_CALIB = 1, 3, 4
COUNTERS = ("lm.moe.held_rows", "lm.moe.row_tiles", "lm.moe.tile_rows")


def linear_of(w, key, cfg: dict):
    """The analog layer of master weights ``w [..., K, N]`` in the
    program's layout (as ``models/lm.py``'s ``linear``): per-column
    weight LSB, activation LSB (set later by calibration), analog gain,
    the chip's rank-1 synapse gain and per-chunk ADC offsets."""
    import jax
    import jax.numpy as jnp

    noise = cfg["noise"]
    rows = cfg["analog"]["chunk_rows"]
    if noise["mode"] != "rank1":
        raise ValueError(f"noise mode {noise['mode']!r}")
    stack, (k, n) = w.shape[:-2], w.shape[-2:]
    kr, kc, ko = jax.random.split(key, 3)
    w_scale = jnp.maximum(jnp.abs(w).max(-2, keepdims=True), 1e-8) / 63.0
    code_rms = jnp.sqrt(jnp.mean((w / w_scale) ** 2, axis=(-2, -1)) + 1e-6)
    partial_rms = np.sqrt(float(rows)) * 9.0 * code_rms
    s = noise["gain_std"] / np.sqrt(2.0)
    return {
        "w": w,
        "w_scale": w_scale,
        "a_scale": jnp.full(stack, 1.0 / 31.0, jnp.float32),
        "gain": jnp.minimum(1.0, 127.0 / (3.0 * partial_rms + 1e-6)),
        "fpn": {
            "row_gain": 1.0 + s * jax.random.normal(kr, stack + (k,)),
            "col_gain": 1.0 + s * jax.random.normal(kc, stack + (n,)),
            "chunk_offset": noise["offset_std"] * jax.random.normal(
                ko, stack + (opcount.chunks(k, rows), n)),
        },
    }


def make_weights(cfg: dict, key):
    """The whole model in one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    d, v = cfg["hidden_size"], cfg["vocab_size"]
    ff, eff = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    dh = d // cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"] * dh
    held = np.asarray(cfg["held_expert_ids"], np.float32)
    n_held = len(held)

    def lin(k, din, dout, stack=()):
        kw, kp = jax.random.split(k)
        w = jax.random.normal(kw, stack + (din, dout), jnp.float32) \
            / np.sqrt(din)
        return linear_of(w, kp, cfg)

    def norm(n=d):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def swiglu(k, width, stack=()):
        ks = jax.random.split(k, 3)
        return {"up": lin(ks[0], d, width, stack),
                "gate": lin(ks[1], d, width, stack),
                "down": lin(ks[2], width, d, stack)}

    @jax.jit
    def make(key):
        k_emb, k_head, k_layers = jax.random.split(key, 3)
        table = 0.02 * jax.random.normal(k_emb, (v, d), jnp.float32)
        layers = {}
        for i, (mixer, f) in enumerate(opcount_lfm2.kinds(cfg)):
            ks = jax.random.split(jax.random.fold_in(k_layers, i), 6)
            p = {"ln1": norm(), "ln2": norm()}
            if mixer == "conv":
                p["conv"] = {
                    "in_proj": lin(ks[0], d, 3 * d),
                    "conv_w": jax.random.normal(
                        ks[1], (cfg["conv_L_cache"], d)) / np.sqrt(
                            cfg["conv_L_cache"]),
                    "out_proj": lin(ks[2], d, d)}
            else:
                p["attn"] = {"wq": lin(ks[0], d, d), "wk": lin(ks[1], d, nkv),
                             "wv": lin(ks[2], d, nkv), "wo": lin(ks[3], d, d),
                             "q_norm": norm(dh), "k_norm": norm(dh)}
            if f == "mlp":
                p["mlp"] = swiglu(ks[4], ff)
            else:
                kr, kb, ke = jax.random.split(ks[5], 3)
                n_exp = cfg["num_experts_published"]
                p["moe"] = {
                    "router": {"w": jax.random.normal(kr, (d, n_exp))
                               / np.sqrt(d)},
                    "expert_bias": 0.01 * jax.random.normal(kb, (n_exp,)),
                    "held": jnp.asarray(held),
                    "experts": swiglu(ke, eff, (n_held,))}
            layers[f"l{i}"] = p
        return {"embed": {"table": table}, "layers": layers,
                "final_norm": norm(),
                "lm_head": linear_of(table.T, k_head, cfg)}

    return make(key)


def arch(cfg: dict):
    """The program's architecture of this configuration (raises on a
    program without per-layer kinds, short convolution or held
    experts)."""
    from repro.configs.base import ArchConfig

    d = cfg["hidden_size"]
    return ArchConfig(
        name=cfg["name"], family="hybrid", n_layers=cfg["num_hidden_layers"],
        d_model=d, n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=d // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        norm="rmsnorm", act="swiglu", rope_theta=cfg["rope_theta"],
        layer_kinds=tuple(f"{m}_{f}" for m, f in opcount_lfm2.kinds(cfg)),
        conv_taps=cfg["conv_L_cache"], qk_norm=True,
        n_experts=cfg["num_experts_published"],
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        held_experts=cfg["num_experts"])


class System:
    """The program under test, built for one cell and one seed."""

    def __init__(self, cfg: dict, traffic: dict, seeds, peak: dict):
        from repro.configs.base import RunConfig
        from repro.core.analog import AnalogConfig
        from repro.serve.engine import ServeEngine

        self.cfg, self.traffic, self.peak = cfg, traffic, peak
        self.arch = arch(cfg)
        a = cfg["analog"]
        self.run = RunConfig(
            analog=AnalogConfig(mode=a["mode"], use_pallas=a["use_pallas"],
                                signed_input=a["signed_input"],
                                act_calib=a["act_calib"],
                                chunk_rows=a["chunk_rows"]),
            activation_dtype=a["activation_dtype"])
        weights = make_weights(cfg, seeds.key(STREAM_WEIGHTS))
        calib = seeds.rng(STREAM_CALIB).integers(
            0, cfg["vocab_size"], (1, traffic["request"]["prompt_tokens"]))
        self.weights = spec.reference("lfm2").calibrate(weights, cfg,
                                                        calib)
        self.engine = ServeEngine(self.arch, self.run, self.weights,
                                  batch_size=int(traffic["batch"]),
                                  max_len=int(traffic["max_len"]))
        self.rng = seeds.rng(STREAM_REQUESTS)
        self.uid = 0
        self.annotate = False
        self.reset_counts()

    def request(self, sizes: dict):
        from repro.serve.engine import Request

        self.uid += 1
        prompt = self.rng.integers(0, self.cfg["vocab_size"],
                                   sizes["prompt_tokens"]).astype(np.int32)
        return Request(uid=self.uid, prompt=prompt,
                       max_new_tokens=sizes["new_tokens"])

    def call(self, requests) -> int:
        from repro import obs
        from repro.obs import metrics

        reg = metrics.registry()
        before = [reg.counter(n).value for n in COUNTERS]
        with obs.collect("lfm2.call"):
            done = self.engine.serve(list(requests))
        rows, tiles, tile_rows = (reg.counter(n).value - b
                                  for n, b in zip(COUNTERS, before))
        prompts = [r.prompt for r in done]
        outs = [r.output for r in done]
        self.batches.append((prompts, outs, rows))
        self.moe["held_rows"] += rows
        self.moe["row_tiles"] += tiles
        self.moe["tile_rows"] += tile_rows
        self._count(prompts, outs, rows)
        return int(sum(len(o) for o in outs))

    def _count(self, prompts, outs, rows: int) -> None:
        """The call's work; its routed rows are split over the steps in
        proportion to their tokens."""
        b, p = len(prompts), max(len(x) for x in prompts)
        steps = [(p, 0)] + [(1, p + t)
                            for t in range(max(len(o) for o in outs) - 1)]
        tokens = sum(q for q, _ in steps)
        k = self.kernels
        for q, start in steps:
            opcount_lfm2.step(self.cfg, b, q, start, rows * q / tokens,
                              self.peak, k["analog_mvm"], k["expert_mvm"],
                              self.step)

    def reset_counts(self) -> None:
        self.batches = []            # (prompts [B, P], served [B, T], rows)
        self.kernels = {"analog_mvm": opcount.Work(),
                        "expert_mvm": opcount.Work()}
        self.step = opcount.Work()
        self.moe = {"held_rows": 0, "row_tiles": 0, "tile_rows": 0}

    def warm(self) -> None:
        """Serve one batch at each of the cell's prompt lengths: prefill
        and, where the cell generates more than one token, a decode step
        (a decode step's shape does not depend on its position)."""
        from chipbench.traffic import sizes

        shapes = sizes(self.traffic)
        for p in shapes["prompt_tokens"]:
            for t in sorted({min(t, 2) for t in shapes["new_tokens"]}):
                self.call([self.request({"prompt_tokens": p,
                                         "new_tokens": t})
                           for _ in range(int(self.traffic["batch"]))])
        self.reset_counts()

    def free_program(self) -> None:
        """Drop the engine and its baked plans before the reference runs."""
        self.engine = None


def build(cfg: dict, traffic: dict, seeds, peak: dict) -> System:
    system = System(cfg, traffic, seeds, peak)
    system.warm()
    return system
