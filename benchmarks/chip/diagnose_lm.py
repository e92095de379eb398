"""Where the LM program and the plain reference part, on one batch.

    python3 benchmarks/chip/diagnose_lm.py [--seed N] [--batch B] [--prompt P]

One process, one seed, one batch of prompts through prefill, at the
widths of ``configs/stablelm-3b-4l.json``.  Each line compares two sets
of last-position prefill logits (or of one layer's outputs) by relative
L2 distance, largest absolute difference, number of differing entries
and top-1 agreement:

- the reference in bfloat16 against the reference (the control's size);
- the reference under a global ``jax_default_matmul_precision=highest``
  against the reference (its dots state their precision);
- the program (``ServeEngine`` prefill, Pallas kernels) against the
  reference;
- layer 0's fused QKV dispatch, Pallas and the program's jnp path, against
  the reference's three analog layers on the same input;
- the program on its jnp path, and the program built and traced under a
  global ``jax_default_matmul_precision=highest``, against the reference.

A diagnostic to run by hand on a TPU, not part of a benchmark run.  Unlike the
references it drives program internals (``exec.run.run_layer``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import numpy as np  # noqa: E402

from chipbench import device, spec  # noqa: E402


def compare(a, b) -> dict:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    d = np.abs(a - b)
    return {"rel_l2": float(np.linalg.norm(a - b) / np.linalg.norm(b)),
            "max_abs": float(d.max()), "n_diff": int((d > 0).sum()),
            "top1": float((a.argmax(-1) == b.argmax(-1)).mean())}


def diagnose(cfg: dict, seed: int, batch: int, prompt: int, peak: dict):
    import jax
    import jax.numpy as jnp

    from repro.exec.plan import find_group
    from repro.exec.run import run_layer
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine

    drv, ref = spec.driver("lm"), spec.reference("lm")
    traffic = {"batch": batch, "max_len": prompt,
               "request": {"prompt_tokens": prompt, "new_tokens": 1}}
    system = drv.System(cfg, traffic, device.Seeds(seed), peak)
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (batch, prompt)).astype(np.int32))
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    shape = (cfg["num_hidden_layers"], batch, prompt,
             cfg["num_key_value_heads"], hd)
    cache = (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
    w = system.weights

    def reference(precision):
        return ref.Replay(w, cfg, precision).prefill(w, toks, cache)[0]

    def program(engine):
        c = T.init_lm_cache(engine.cfg, batch, prompt, dtype=jnp.float32)
        return engine.prefill(engine.params, {"tokens": toks}, c)[0]

    want = reference("highest")
    out = {"reference_bfloat16": compare(reference("bfloat16"), want)}
    with jax.default_matmul_precision("highest"):
        out["reference_global_highest"] = compare(reference("highest"),
                                                  want)
    out["program"] = compare(program(system.engine), want)

    first = lambda tree: jax.tree.map(lambda a: a[0], tree)  # noqa: E731
    layer = first(w["layers"]["l0"])
    h = ref.layer_norm(w["embed"]["table"][toks], layer["ln1"],
                       cfg["layer_norm_eps"]).reshape(batch * prompt, -1)
    rows = cfg["analog"]["chunk_rows"]
    qkv_ref = jnp.concatenate(
        [ref.analog(h, layer["attn"][k], rows=rows, precision="highest")
         for k in ("wq", "wk", "wv")], -1)
    attn = system.engine.params["layers"]["l0"]["attn"]
    fused = first(find_group(attn.get("_groups"), "column_concat",
                             ("wq", "wk", "wv")).fused)
    acfg = system.run.analog
    for name, a in (("qkv_layer0_pallas", acfg),
                    ("qkv_layer0_jnp", acfg.replace(use_pallas=False))):
        got = jax.jit(lambda lp, x, a=a: run_layer(lp, x, a))(fused, h)
        out[name] = compare(got, qkv_ref)

    jnp_run = dataclasses.replace(system.run,
                                  analog=acfg.replace(use_pallas=False))
    out["program_jnp"] = compare(program(ServeEngine(
        system.arch, jnp_run, w, batch_size=batch, max_len=prompt)), want)
    with jax.default_matmul_precision("highest"):
        out["program_global_highest"] = compare(program(ServeEngine(
            system.arch, system.run, w, batch_size=batch,
            max_len=prompt)), want)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=424242)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=128)
    args = ap.parse_args(argv)
    with open(HERE / "configs" / "stablelm-3b-4l.json") as f:
        cfg = json.load(f)
    try:
        devices = device.require(1)
    except device.NoChip as e:
        device.log(f"diagnose_lm: {e}; nothing was run")
        return 1
    device.enable_compile_cache()
    found = diagnose(cfg, args.seed, args.batch, args.prompt,
                     spec.peak(devices[0].device_kind))
    for k, v in found.items():
        print(k, json.dumps(v), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
