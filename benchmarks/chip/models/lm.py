"""System driver for configurations of kind ``lm``: a decoder LM whose
every parameter matmul runs on the emulated analog chip, served by the
program's ``ServeEngine(...).serve(requests)``.

A request is a prompt of random token ids and a number of tokens to
generate greedily.  A call of the system is one ``serve`` of up to
``batch`` requests: padded prefill, then batched decode steps, each
token read back on the host.

The weights and the chip's fixed pattern are the benchmark's, made from
the seed in one jitted call on the device; the program gets them as
inputs and bakes its own plans from them.
"""
from __future__ import annotations

import numpy as np

from chipbench import opcount

STREAM_WEIGHTS, STREAM_REQUESTS = 1, 3


def linear(key, k: int, n: int, cfg: dict, stack: tuple = ()):
    """One analog linear layer in the program's layout: fp32 masters,
    per-column weight LSB, activation LSB, analog gain (3 sigma of a
    typical chunk's partial sum inside the ADC range), the chip's
    fixed pattern (rank-1 synapse gain, per-chunk ADC offsets)."""
    import jax
    import jax.numpy as jnp

    noise = cfg["noise"]
    rows = cfg["analog"]["chunk_rows"]
    if noise["mode"] != "rank1":
        raise ValueError(f"noise mode {noise['mode']!r}")
    kw, kr, kc, ko = jax.random.split(key, 4)
    w = jax.random.normal(kw, stack + (k, n), jnp.float32) / np.sqrt(k)
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
    """The whole model in one jitted call on the device, in the program's
    layout: token embedding, ``num_hidden_layers`` stacked layers
    (LayerNorm, fused-able q/k/v and o, LayerNorm, SwiGLU up/gate/down),
    final LayerNorm and an untied head."""
    import jax
    import jax.numpy as jnp

    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    nl = (cfg["num_hidden_layers"],)

    def norm(stack=()):
        return {"scale": jnp.ones(stack + (d,), jnp.float32),
                "bias": jnp.zeros(stack + (d,), jnp.float32)}

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 9)
        layer = {
            "ln1": norm(nl),
            "attn": {"wq": linear(ks[0], d, d, cfg, nl),
                     "wk": linear(ks[1], d, kv, cfg, nl),
                     "wv": linear(ks[2], d, kv, cfg, nl),
                     "wo": linear(ks[3], d, d, cfg, nl)},
            "ln2": norm(nl),
            "mlp": {"up": linear(ks[4], d, ff, cfg, nl),
                    "down": linear(ks[5], ff, d, cfg, nl),
                    "gate": linear(ks[6], d, ff, cfg, nl)},
        }
        return {
            "embed": {"table": 0.02 * jax.random.normal(ks[7], (v, d))},
            "layers": {"l0": layer},
            "final_norm": norm(),
            "lm_head": linear(ks[8], d, v, cfg),
        }

    return make(key)


def arch(cfg: dict):
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        norm="layernorm", act="swiglu", rope_theta=cfg["rope_theta"])


class System:
    """The program under test, built for one cell and one seed."""

    def __init__(self, cfg: dict, traffic: dict, seeds, peak: dict):
        from repro.configs.base import RunConfig
        from repro.core.analog import AnalogConfig
        from repro.serve.engine import ServeEngine

        self.cfg, self.traffic, self.peak = cfg, traffic, peak
        a = cfg["analog"]
        self.arch = arch(cfg)
        self.run = RunConfig(
            analog=AnalogConfig(mode=a["mode"], use_pallas=a["use_pallas"],
                                signed_input=a["signed_input"],
                                act_calib=a["act_calib"],
                                chunk_rows=a["chunk_rows"]),
            activation_dtype=a["activation_dtype"])
        self.weights = make_weights(cfg, seeds.key(STREAM_WEIGHTS))
        self.engine = ServeEngine(self.arch, self.run, self.weights,
                                  batch_size=int(traffic["batch"]),
                                  max_len=int(traffic["max_len"]))
        self.rng = seeds.rng(STREAM_REQUESTS)
        self.uid = 0
        self.batches = []            # (prompts [B, P], served [B, T])
        self.kernels = {"analog_mvm": opcount.Work()}
        self.step = opcount.Work()
        self.annotate = False

    def request(self, sizes: dict):
        from repro.serve.engine import Request

        self.uid += 1
        prompt = self.rng.integers(0, self.cfg["vocab_size"],
                                   sizes["prompt_tokens"]).astype(np.int32)
        return Request(uid=self.uid, prompt=prompt,
                       max_new_tokens=sizes["new_tokens"])

    def call(self, requests) -> int:
        done = self.engine.serve(list(requests))
        prompts = [r.prompt for r in done]
        outs = [r.output for r in done]
        self.batches.append((prompts, outs))
        self._count(prompts, outs)
        return int(sum(len(o) for o in outs))

    def _count(self, prompts, outs) -> None:
        b, p = len(prompts), max(len(x) for x in prompts)
        mvm = self.kernels["analog_mvm"]
        opcount.lm_step(self.cfg, b, p, 0, self.peak, mvm, self.step)
        for t in range(max(len(o) for o in outs) - 1):
            opcount.lm_step(self.cfg, b, 1, p + t, self.peak, mvm,
                            self.step)

    def reset_counts(self) -> None:
        self.batches.clear()
        self.kernels = {k: opcount.Work() for k in self.kernels}
        self.step = opcount.Work()

    def warm(self) -> None:
        """Serve one batch at each of the cell's prompt lengths: prefill at
        that length and, where the cell generates more than one token,
        two decode steps (a decode step's shape does not depend on its
        position)."""
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
