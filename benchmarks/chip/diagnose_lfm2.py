"""Where the ``lfm2`` program and the plain reference part, on one batch.

    python3 benchmarks/chip/diagnose_lfm2.py --chain 1 [--seed N] [--batch B] [--prompt P]
    python3 benchmarks/chip/diagnose_lfm2.py --serve <new tokens> [...]

One process, one seed, one batch of prompts, at the widths of
``configs/lfm2-8b-a1b-6l.json`` with the cell's static LSBs.

- ``--chain``: the program's layers (``models.transformer._layer_apply``
  over the lowered tree, a prefill through an empty cache) chained on
  their own outputs against the reference's chain, row by row, and each
  program layer on the reference's own input (a difference there is that
  layer's own); then the engine's prefill, the chain's logits and the
  reference's.
- ``--serve``: the batch served by the engine (prefill, then decode
  steps on its own greedy tokens) and replayed by the reference: per
  step the logits' distance, the served tokens' gaps and the routed
  (token, held expert) pairs counted by each side.

Each comparison gives the largest absolute difference and the number of
differing entries.  A diagnostic to run by hand on a TPU, not part of a
benchmark run; it drives program internals.
"""
from __future__ import annotations

import argparse
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
    return {"rel_l2": float(np.linalg.norm(a - b)
                            / max(np.linalg.norm(b), 1e-30)),
            "max_abs": float(d.max()), "n_diff": int((d > 0).sum()),
            "n": int(d.size)}


def serve(cfg: dict, seed: int, batch: int, prompt: int, new: int) -> list:
    """One batch served by the program, replayed by the reference: per
    step, the logits' distance, the served tokens' gaps and the routed
    (token, held expert) pairs of each side."""
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.obs import metrics

    drv, ref = spec.driver("lfm2"), spec.reference("lfm2")
    seeds = device.Seeds(seed)
    traffic = {"batch": batch, "max_len": prompt + new,
               "request": {"prompt_tokens": prompt, "new_tokens": new}}
    peak = {"int8_ops_per_s": 1, "hbm_bytes_per_s": 1}
    system = drv.System(cfg, traffic, seeds, peak)
    eng = system.engine
    reqs = [system.request({"prompt_tokens": prompt, "new_tokens": new})
            for _ in range(batch)]
    toks = np.stack([r.prompt for r in reqs])
    from repro.models import transformer as T

    cache = T.init_lm_cache(eng.cfg, batch, prompt + new, dtype=jnp.float32)
    rows = metrics.registry().counter("lm.moe.held_rows")
    steps, counts = [], []
    with obs.collect("diag"):
        before = rows.value
        logits, cache = eng.prefill(eng.params, {"tokens": jnp.asarray(toks)},
                                    cache)
        pending = []
        eng._expert_stats(cache, batch * prompt, pending)
        eng._count_experts(pending)
        counts.append(rows.value - before)
        steps.append(np.asarray(logits))
        served = [np.asarray(jnp.argmax(logits, -1))]
        for _ in range(new - 1):
            before = rows.value
            logits, cache = eng.decode(eng.params,
                                       jnp.asarray(served[-1])[:, None],
                                       cache)
            pending = []
            eng._expert_stats(cache, batch, pending)
            eng._count_experts(pending)
            counts.append(rows.value - before)
            steps.append(np.asarray(logits))
            served.append(np.asarray(jnp.argmax(logits, -1)))
    served = np.stack(served, 1)
    system.free_program()
    replay = ref.Replay(system.weights, cfg, "highest")
    want, routes = replay.run(toks, served)
    held = np.asarray(cfg["held_expert_ids"])
    out = []
    for i, (got, w) in enumerate(zip(steps, want)):
        w = np.asarray(w)
        gap = w.max(-1) - np.take_along_axis(w, served[:, i:i + 1], -1)[:, 0]
        pairs = ref.held_pairs([routes[i]], held,
                               cfg["num_experts_per_tok"])
        out.append({"step": i, **compare(got, w),
                    "gaps": [float(g) for g in gap],
                    "program_pairs": int(counts[i]),
                    "reference_pairs": pairs})
    return out


def chain(cfg: dict, seed: int, batch: int, prompt: int) -> list:
    """The program's layers chained on their own outputs against the
    reference's chain, row by row, and each program layer on the
    reference's input: where a row first parts."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    drv, ref = spec.driver("lfm2"), spec.reference("lfm2")
    seeds = device.Seeds(seed)
    traffic = {"batch": batch, "max_len": prompt,
               "request": {"prompt_tokens": prompt, "new_tokens": 1}}
    system = drv.System(cfg, traffic, seeds,
                        {"int8_ops_per_s": 1, "hbm_bytes_per_s": 1})
    arch, run, w = system.arch, system.run, system.weights
    lowered = system.engine.params
    reqs = [system.request({"prompt_tokens": prompt, "new_tokens": 1})
            for _ in range(batch)]
    tokens = jnp.asarray(np.stack([r.prompt for r in reqs]))
    model = ref.Model(cfg, "highest")
    positions = jnp.broadcast_to(jnp.arange(prompt)[None], (batch, prompt))
    state = ref.init_state(cfg, batch, prompt)

    @functools.partial(jax.jit, static_argnums=3)
    def ref_layer(p, x, st, i):
        return _ref_layer(model, ref, cfg, p, x, st, i)

    def rows(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        d = np.abs(a - b).reshape(a.shape[0], -1)
        return {"n_diff": [int(v) for v in (d > 0).sum(1)],
                "max_abs": [float(v) for v in d.max(1)]}

    out = []
    xr = xp = w["embed"]["table"][tokens]
    for i, (mixer, ff) in enumerate(ref.kinds(cfg)):
        kind = f"{mixer}_{ff}"
        lp = lowered["layers"][f"l{i}"]
        cache = T._layer_cache(kind, arch, batch, prompt, jnp.float32)
        prog = jax.jit(lambda lp, x, c, kind=kind: T._layer_apply(
            lp, kind, x, cfg=arch, run=run, positions=positions,
            cache=c, key=None)[0])
        alone = prog(lp, xr, cache)
        xr_next = ref_layer(w["layers"][f"l{i}"], xr, state[i], i)
        xp = prog(lp, xp, cache)
        out.append({"layer": i, "kind": kind,
                    "own_input": rows(alone, xr_next),
                    "chained": rows(xp, xr_next)})
        xr = xr_next
    logits, _ = system.engine.prefill(
        lowered, {"tokens": tokens},
        T.init_lm_cache(arch, batch, prompt, dtype=jnp.float32))
    want, _, _ = jax.jit(lambda ww, t, st: model(ww, t, st, 0))(
        w, tokens, state)
    head = jax.jit(lambda lp, x: T.L.linear_apply(
        lp["lm_head"], T.L.norm_apply(lp["final_norm"], x[:, -1:]),
        run.analog))(lowered, xp)[:, 0]
    out.append({"engine_vs_reference": rows(logits, want),
                "chain_vs_reference": rows(head, want),
                "engine_vs_chain": rows(logits, head)})
    return out


def _ref_layer(model, ref, cfg, p, x, st, i):
    mixer, ff = ref.kinds(cfg)[i]
    eps = cfg["norm_eps"]
    b, s, d = x.shape
    u = ref.rms_norm(x, p["ln1"], eps)
    if mixer == "conv":
        y, _ = model.conv("c", p["conv"], u, st)
    else:
        y, _ = model.attn("a", p["attn"], u, st, 0)
    x = x + y
    u = ref.rms_norm(x, p["ln2"], eps).reshape(b * s, d)
    y = (model.ffn("f", p["mlp"], u) if ff == "mlp"
         else model.moe("m", p["moe"], u)[0])
    return x + y.reshape(b, s, d)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=424242)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=1024)
    ap.add_argument("--serve", type=int, default=0,
                    help="new tokens: serve one batch and replay it")
    ap.add_argument("--chain", type=int, default=0)
    args = ap.parse_args(argv)
    with open(HERE / "configs" / "lfm2-8b-a1b-6l.json") as f:
        cfg = json.load(f)
    if args.serve:
        lines = serve(cfg, args.seed, args.batch, args.prompt, args.serve)
    elif args.chain:
        lines = chain(cfg, args.seed, args.batch, args.prompt)
    else:
        ap.error("give --chain 1 or --serve <new tokens>")
    for line in lines:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
