"""Chip smoke test: drive the system's main paths once on a TPU and check
what comes out against the jnp reference.

    python chip_smoke.py              # one chip: ECG megakernel + LM server
    python chip_smoke.py --chips 4    # only the LM server sharded over 4 chips

One chip runs three phases in one process:

- device: require a TPU; print its kind and the device count.
- ecg: the paper's ECG classifier at its published shape (``ECGConfig()``)
  on seeded synthetic patient windows.  Pallas max-min preprocessing, blind
  calibration of every layer's (virtual) chip, then the conv->fc1->fc2
  chain compiled with ``AnalogConfig(use_pallas=True)`` and applied at
  B = 1 and B = 256.  It must take the megakernel route (one dispatch, one
  ``tpu_custom_call`` in the compiled HLO) and agree with the same plan
  replayed by the jnp reference at "highest" matmul precision.
- lm: a ``ServeEngine`` serving stablelm-3b at its published widths, depth
  cut to 4 layers, on the ``analog_faithful`` Pallas path.  It answers 4
  seeded requests; its prefill logits must agree with the jnp reference
  forward of the same model at "highest" precision.

``--chips 4`` runs only the sharded phase: the same server under a
(data=1, model=4) mesh with sharded plan leaves, compared with the same
server on one device.

Each phase prints one line of findings.  Any failed check raises, so the
script exits non-zero.  The last line of standard output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# --- tolerances -----------------------------------------------------------
# The reference runs the same plan through the jnp path at "highest"
# matmul precision.  On the chip the Pallas kernels contract fp32 operands
# at full precision too, so the two differ only in fp32 summation order:
# an ADC code flips only where a chunk's sum lies within an ulp or so of a
# rounding boundary.  On a TPU v5e the two agreed exactly (every code, every
# class, LM logits to the bit).  Each limit sits between that reading and
# two that must fail: the kernels with bf16 operands, whose rounding of the
# gain-folded effective weights read (TPU v5e) ECG B=1 mean 0.1 / max 1 /
# agreement 1.0, B=256 mean 0.591 / max 5 / agreement 0.965, LM 0.338; and
# an un-quantised float forward, ECG mean 2.6 / max 12 / agreement 0.87
# over 256 windows, LM 0.357.  The phases check that the float forward
# fails every limit.
#
# ECG: final-layer ADC codes, in LSB, and the predicted class.
ECG_MEAN_CODE_TOL = 0.05       # mean |chip - ref|: bf16 fails at B=1 and 256
ECG_MAX_CODE_TOL = 1.0         # worst output: one summation-order flip
ECG_MIN_CLASS_AGREE = 0.99     # share of windows with the same class
# The float forward is held to them over this many windows: one window
# cannot fail a share of agreement.
ECG_FLOAT_WINDOWS = 256
# LM: relative L2 distance of the last-position prefill logits.
LM_REL_TOL = 0.02

LM_ARCH = "stablelm-3b"
LM_LAYERS = 4
LM_REDUCED = (
    "depth 32 -> 4 layers: fp32 masters are 317 MB per layer plus 1.03 GB "
    "for embedding and head, and the replay materialises fp32 w_eff, so 32 "
    "layers do not fit the 16 GB of one v5e"
)


class SmokeFailure(AssertionError):
    """A phase's output is wrong: the run must exit non-zero."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _kernel_calls(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


# --------------------------------------------------------------------- ECG
def ecg_phase(*, seed: int = 0, batches=(1, 256)) -> dict:
    """The paper's ECG path at its published shape; see the module doc."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api, calib
    from repro.core.analog import AnalogConfig
    from repro.data import ecg_synth
    from repro.data.preprocess import preprocess
    from repro.exec.run import dispatch_count, reset_dispatch_count
    from repro.models import ecg as ECG

    cfg = ECG.ECGConfig()
    n = max(max(batches), ECG_FLOAT_WINDOWS)
    raw, _ = ecg_synth.make_dataset(
        ecg_synth.ECGDatasetConfig(n_train=n, seed=seed), split="train")
    raw = jnp.asarray(raw)
    x = preprocess(raw, use_pallas=True)
    check(bool((x == preprocess(raw, use_pallas=False)).all()),
          "Pallas max-min preprocessing differs from the jnp reference")
    check(x.shape == (n, cfg.in_channels, cfg.in_len),
          f"preprocessed windows have shape {x.shape}")

    params = ECG.ecg_init(jax.random.PRNGKey(seed), cfg)
    spec = ECG.ecg_module_spec(cfg, epilogue="relu_shift")
    key = jax.random.PRNGKey(seed + 1)
    chips = calib.model_chips(spec, params, key)
    snap = calib.calibrate_model(spec, params, key, chips=chips)
    acfg = AnalogConfig(use_pallas=True)
    model = api.compile(spec, params, acfg, calibration=snap)
    plan = model.lower()
    # the reference replays the SAME plan: identical leaves, jnp execution
    ref = dataclasses.replace(
        model, lowered=dataclasses.replace(
            plan, cfg=acfg.replace(use_pallas=False)))
    flt = api.compile(spec, params, AnalogConfig(mode="digital"))

    last = plan.layers[-1]
    lsb = np.broadcast_to(
        np.asarray(last.w_scale, np.float64).reshape(-1)
        / np.asarray(last.gain, np.float64), (last.n,))

    def final_codes(m, xb):
        out = jax.jit(m.run_stack)(
            ECG._im2col(xb, cfg.conv_taps, cfg.conv_stride))
        return np.asarray(out, np.float64) / lsb

    def classes(logits):
        return np.asarray(logits).argmax(-1)

    # the limits must fail a float forward of the same weights
    with jax.default_matmul_precision("highest"):
        codes_ref = final_codes(ref, x)
        classes_ref = classes(jax.jit(ref.apply)(x))
        flt_diff = np.abs(final_codes(flt, x) - codes_ref)
        flt_agree = float((classes(jax.jit(flt.apply)(x))
                           == classes_ref).mean())
    for name, passes in (
            ("mean code", flt_diff.mean() <= ECG_MEAN_CODE_TOL),
            ("max code", flt_diff.max() <= ECG_MAX_CODE_TOL),
            ("class agreement", flt_agree >= ECG_MIN_CLASS_AGREE)):
        check(not passes, f"the float forward passes the {name} limit "
              f"(mean {flt_diff.mean()}, max {flt_diff.max()} LSB, "
              f"agreement {flt_agree}): the limit is too loose")
    print(f"ecg: float_forward over {n} windows mean_code_diff="
          f"{flt_diff.mean()} max_code_diff={flt_diff.max()} "
          f"class_agree={flt_agree} (fails every limit)", flush=True)

    found = {"float_forward": dict(mean_code_diff=float(flt_diff.mean()),
                                   max_code_diff=float(flt_diff.max()),
                                   class_agree=flt_agree)}
    for b in batches:
        xb = x[:b]
        reset_dispatch_count()
        lowered = jax.jit(model.apply).lower(xb)
        dispatches = dispatch_count()
        check(dispatches == 1,
              f"B={b}: the ECG chain traced {dispatches} analog dispatches, "
              "not the single megakernel dispatch")
        compiled = lowered.compile()
        kernels = _kernel_calls(compiled.as_text())
        if jax.devices()[0].platform == "tpu":   # CPU: interpret mode
            check(kernels == 1, f"B={b}: compiled HLO holds {kernels} "
                  "tpu_custom_call ops, expected the one megakernel")
        logits = compiled(xb)
        check(np.isfinite(np.asarray(logits)).all(), f"B={b}: non-finite")
        check(logits.shape == (b, cfg.classes), f"B={b}: {logits.shape}")
        with jax.default_matmul_precision("highest"):
            codes_ref_b = final_codes(ref, xb)
            classes_ref_b = classes(jax.jit(ref.apply)(xb))
        diff = np.abs(np.round(final_codes(model, xb))
                      - np.round(codes_ref_b))
        agree = float((classes(logits) == classes_ref_b).mean())
        check(diff.mean() <= ECG_MEAN_CODE_TOL,
              f"B={b}: mean |code diff| {diff.mean()} > {ECG_MEAN_CODE_TOL}")
        check(diff.max() <= ECG_MAX_CODE_TOL,
              f"B={b}: max |code diff| {diff.max()} > {ECG_MAX_CODE_TOL}")
        check(agree >= ECG_MIN_CLASS_AGREE,
              f"B={b}: class agreement {agree} < {ECG_MIN_CLASS_AGREE}")
        found[b] = dict(dispatches=dispatches, tpu_custom_call=kernels,
                        max_code_diff=float(diff.max()),
                        mean_code_diff=float(diff.mean()),
                        flipped_share=float((diff > 0).mean()),
                        class_agree=agree)
        print(f"ecg: B={b} dispatches={dispatches} "
              f"tpu_custom_call={kernels} "
              f"max_code_diff={diff.max()} mean_code_diff={diff.mean()} "
              f"flipped_share={(diff > 0).mean()} class_agree={agree} "
              f"(tol mean<={ECG_MEAN_CODE_TOL} max<={ECG_MAX_CODE_TOL} "
              f"agree>={ECG_MIN_CLASS_AGREE})", flush=True)
    return found


# ---------------------------------------------------------------------- LM
def lm_config(arch: str = LM_ARCH, n_layers: int = LM_LAYERS):
    from repro import configs

    return dataclasses.replace(configs.get_arch(arch), n_layers=n_layers)


def lm_run(acfg):
    """The served run config.  Activations between the analog layers are
    fp32: in bf16 the glue's rounding depends on the reduction order, and
    the 5-bit re-quantization at the next analog layer amplifies it (on 4
    CPU devices a (1, 4) mesh moved bf16-activation prefill logits 11.5 %
    from one device, fp32 ones not at all)."""
    from repro.configs.base import RunConfig

    return RunConfig(analog=acfg, activation_dtype="float32")


def _requests(cfg, *, seed: int, n: int, prompt_lens, max_new: int):
    import numpy as np

    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    return [
        Request(uid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(lo, hi + 1))
                                    ).astype(np.int32),
                max_new_tokens=max_new)
        for i in range(n)
    ]


def _left_padded(reqs):
    import numpy as np

    width = max(len(r.prompt) for r in reqs)
    toks = np.zeros((len(reqs), width), np.int32)
    for i, r in enumerate(reqs):
        toks[i, width - len(r.prompt):] = r.prompt
    return toks


def _serve(cfg, run, params, reqs, max_len):
    """Serve ``reqs`` with a fresh engine; return the engine, the outputs,
    the wall seconds and the engine's own prefill logits for them."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine

    engine = ServeEngine(cfg, run, params, batch_size=len(reqs),
                         max_len=max_len)
    t0 = time.perf_counter()
    done = engine.serve(reqs)
    wall = time.perf_counter() - t0
    toks = jnp.asarray(_left_padded(reqs))
    cache = T.init_lm_cache(cfg, len(reqs), max_len, dtype=jnp.float32)
    logits, _ = engine.prefill(engine.params, {"tokens": toks}, cache)
    return engine, done, wall, jax.device_get(logits)


def _rel(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def lm_phase(cfg, *, seed: int = 0, n_requests: int = 4,
             prompt_lens=(16, 64), max_new: int = 8,
             max_len: int = 128) -> dict:
    """A ServeEngine on the analog_faithful Pallas path; see the module
    doc."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.core.analog import DIGITAL, AnalogConfig
    from repro.models import transformer as T
    from repro.serve.serve_step import serve_prefill

    run = lm_run(AnalogConfig(mode="analog_faithful", use_pallas=True))
    params = T.lm_init(jax.random.PRNGKey(seed), cfg)
    reqs = _requests(cfg, seed=seed, n=n_requests, prompt_lens=prompt_lens,
                     max_new=max_new)
    _, done, wall, logits = _serve(cfg, run, params, reqs, max_len)
    check(len(done) == n_requests, f"served {len(done)} of {n_requests}")
    check(all(len(r.output) == max_new for r in done),
          "a request got fewer new tokens than it asked for")
    check(all(((r.output >= 0) & (r.output < cfg.vocab_size)).all()
              for r in done), "a generated token is outside the vocabulary")

    toks = jnp.asarray(_left_padded(reqs))
    cache = T.init_lm_cache(cfg, n_requests, max_len, dtype=jnp.float32)
    ref_run = lm_run(run.analog.replace(use_pallas=False))
    ref_tree = api.compile(T.lm_module_spec(cfg, params), params,
                           ref_run).lower()
    with jax.default_matmul_precision("highest"):
        logits_ref, _ = jax.jit(functools.partial(
            serve_prefill, cfg=cfg, run=ref_run))(ref_tree, {"tokens": toks},
                                                  cache)
        cache = T.init_lm_cache(cfg, n_requests, max_len, dtype=jnp.float32)
        logits_flt, _ = jax.jit(functools.partial(
            serve_prefill, cfg=cfg, run=lm_run(DIGITAL)))(
                params, {"tokens": toks}, cache)
    logits_ref = jax.device_get(logits_ref)
    check(np.isfinite(logits).all(), "non-finite prefill logits")
    rel = _rel(logits, logits_ref)
    rel_flt = _rel(logits_flt, logits_ref)
    top1 = float((logits.argmax(-1) == logits_ref.argmax(-1)).mean())
    check(rel <= LM_REL_TOL,
          f"prefill logits are {rel} (relative L2) from the reference, "
          f"> {LM_REL_TOL}")
    check(rel_flt > LM_REL_TOL,
          f"the float forward is only {rel_flt} from the reference: the "
          "tolerance is too loose")
    new = sum(len(r.output) for r in done)
    print(f"lm: {cfg.name} d={cfg.d_model} heads={cfg.n_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} layers={cfg.n_layers} "
          f"requests={len(done)} prompt_lens="
          f"{[len(r.prompt) for r in reqs]} new_tokens={new} "
          f"prefill_rel_l2={rel} top1_agree={top1} "
          f"float_forward_rel_l2={rel_flt} (tol rel<={LM_REL_TOL})",
          flush=True)
    print(f"lm: reduced: {LM_REDUCED}", flush=True)
    print(f"lm: note: {new} new tokens in {wall:.3f} s of host wall clock, "
          "compilation included; not a throughput measurement", flush=True)
    return dict(prefill_rel_l2=rel, top1_agree=top1,
                float_forward_rel_l2=rel_flt, new_tokens=new)


def sharded_lm_phase(cfg, *, n_devices: int, seed: int = 0,
                     n_requests: int = 4, prompt_lens=(16, 64),
                     max_new: int = 8, max_len: int = 128) -> dict:
    """The LM server under a (data=1, model=n_devices) mesh against the
    same server on one device."""
    import jax
    import numpy as np

    from repro.core.analog import AnalogConfig
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T

    run = lm_run(AnalogConfig(mode="analog_faithful", use_pallas=True))
    params = T.lm_init(jax.random.PRNGKey(seed), cfg)
    kw = dict(seed=seed, n=n_requests, prompt_lens=prompt_lens,
              max_new=max_new)
    _, done1, _, logits1 = _serve(cfg, run, params, _requests(cfg, **kw),
                                  max_len)
    mesh = make_mesh((1, n_devices), ("data", "model"))
    with shd.use_mesh(mesh):
        engine, done_m, _, logits_m = _serve(cfg, run, params,
                                             _requests(cfg, **kw), max_len)
    # the served plan leaves really are spread over the mesh
    spread = max(len(leaf.sharding.device_set)
                 for leaf in jax.tree.leaves(engine.params))
    check(spread == n_devices,
          f"plan leaves span at most {spread} of {n_devices} devices")
    rel = _rel(logits_m, logits1)
    same_tokens = float(np.mean([
        (a.output == b.output).mean() for a, b in zip(done1, done_m)]))
    check(np.isfinite(logits_m).all(), "non-finite sharded prefill logits")
    check(rel <= LM_REL_TOL,
          f"sharded prefill logits are {rel} (relative L2) from the "
          f"one-device run, > {LM_REL_TOL}")
    print(f"lm-sharded: mesh=(data=1, model={n_devices}) "
          f"plan_leaf_devices={spread} prefill_rel_l2_vs_one_device={rel} "
          f"prefill_bit_exact={bool((logits_m == logits1).all())} "
          f"same_tokens_share={same_tokens} (tol rel<={LM_REL_TOL})",
          flush=True)
    print(f"lm-sharded: reduced: {LM_REDUCED}", flush=True)
    return dict(prefill_rel_l2=rel, same_tokens_share=same_tokens)


# -------------------------------------------------------------------- main
def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the LM server sharded over 4 chips "
                         "and compare it with one chip")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    entries0 = _cache_entries(cache_dir)
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 1:
        ecg_phase()
        print(f"ecg: phase_s={time.perf_counter() - t0:.1f}", flush=True)
        t1 = time.perf_counter()
        lm_phase(lm_config())
        print(f"lm: phase_s={time.perf_counter() - t1:.1f}", flush=True)
    else:
        sharded_lm_phase(lm_config(), n_devices=args.chips)
        print(f"lm-sharded: phase_s={time.perf_counter() - t0:.1f}",
              flush=True)
    print(f"compile-cache: dir={cache_dir} entries_before={entries0} "
          f"entries_after={_cache_entries(cache_dir)} "
          f"total_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
