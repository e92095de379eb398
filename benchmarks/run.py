"""Benchmark driver: one benchmark per paper table/figure + the roofline
report.  ``PYTHONPATH=src python -m benchmarks.run [--full | --smoke]``

| benchmark            | paper artifact                    |
|----------------------|-----------------------------------|
| table1_energy        | Table 1 + Eqs. (1)-(3)            |
| throughput           | Eqs. (1)-(2), §V scaling argument |
| ecg_accuracy         | §IV / Fig. 8 classification       |
| kernels_micro        | (framework) Pallas kernel checks  |
| roofline             | §Roofline dry-run analysis        |

``--smoke`` runs the CI subset (kernel checks + the exec-layer and
transformer-block plan-vs-percall throughputs + the megakernel-vs-
per-layer code-domain chain + the fused attention+MLP block megakernel
+ the rwkv batch_concat and moe expert_stack fusion-group speedups +
the calibrated-snapshot-vs-ideal-bake replay + the fleet vmapped
calibration and remap hot-swap gates) and writes the numbers to
BENCH_smoke.json.

``--full`` additionally trains the ECG CDNN through BOTH inter-layer
chains (float glue vs code-domain relu_shift) and evaluates each on
plans baked two ways: oracle fixed pattern vs measured
CalibrationSnapshot (repro.calib).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.obs import trace as obs_trace


def kernels_micro() -> None:
    """Per-kernel allclose + emulation timing (CSV: name,us_per_call)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    print("\n== kernels micro (interpret mode vs oracle) ==")
    k = jax.random.PRNGKey(0)
    a = jnp.round(jax.random.uniform(k, (256, 512)) * 31)
    w = jnp.round(jax.random.normal(k, (512, 512)) * 20)
    gain = jnp.full((512,), 0.02)
    for faithful in (True, False):
        tag = "faithful" if faithful else "fast"
        with obs_trace.span(f"bench.analog_mvm.{tag}") as sp:
            got = ops.analog_mvm(a, w, gain, None, 128, faithful, True)
            want = ref.analog_mvm_ref(a, w, gain, None, faithful=faithful)
        err = float(abs(got - want).max())
        print(f"analog_mvm[{tag}],{sp.dur_us:.0f}us,max_err={err}")
    x = jax.random.normal(k, (8, 4096))
    with obs_trace.span("bench.maxmin_pool") as sp:
        got = ops.maxmin_pool(x, 32, use_pallas=True)
        want = ref.maxmin_pool_ref(x, 32)
    print(f"maxmin_pool,{sp.dur_us:.0f}us,"
          f"exact={bool((got == want).all())}")


def smoke() -> None:
    """CI subset: kernel sanity + the exec-layer, transformer-block and
    megakernel plan speedups, dumped to BENCH_smoke.json.  Exits non-zero
    (failing the bench-smoke CI job) if plan replay regresses below 1.0x
    vs the per-call path (or the megakernel vs the layer-by-layer
    replay)."""
    from benchmarks import throughput
    from repro.obs import metrics as obs_metrics
    from repro.obs import report as obs_report

    # one obs collector spans the whole smoke run: every _best_of /
    # span measurement lands in BENCH_smoke_obs.jsonl next to the gated
    # BENCH_smoke.json numbers (same timing implementation - ISSUE 9)
    obs_metrics.reset_metrics()
    tr = obs_trace.begin("bench-smoke")
    # static verification FIRST: a dispatch-count / treedef / packing
    # regression fails the job with a named rule + pytree path instead of
    # surfacing as an unexplained slowdown in the timings below.  Run in
    # a subprocess: the sweep compiles ~16 models, and that much jit-cache
    # and heap in THIS process skews the marginal (~1.0-1.3x) timing
    # gates below.  The child runs on the CPU: static verification needs
    # no chip, and a chip belongs to one process (this one).
    gate = subprocess.run(
        [sys.executable, "-m", "repro.verify", "--sweep-only"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if gate.returncode != 0:
        print("\n== static verification (repro.verify) ==")
        print(gate.stdout + gate.stderr)
        print("FAIL: invariant diagnostic(s); not timing a "
              "structurally-regressed build")
        sys.exit(1)
    print("static verification: plans/specs OK")
    kernels_micro()
    pc = throughput.plan_vs_percall_throughput(iters=5)
    print("\n== plan-cached vs per-call requantize (exec layer) ==")
    print(f"{pc['shape']}: dispatches={pc['dispatches']} "
          f"plan {pc['plan_speedup']:.2f}x, "
          f"plan+fused {pc['fused_speedup']:.2f}x")
    tb = throughput.transformer_block_plan_throughput(iters=5)
    print("\n== transformer block: api plan (fused QKV) vs per-call ==")
    print(f"{tb['shape']}: dispatches={tb['dispatches']} "
          f"plan {tb['plan_speedup']:.2f}x, "
          f"lower() once = {tb['lower_us']:.0f}us")
    mk = throughput.megakernel_vs_per_layer_throughput(iters=5)
    print("\n== megakernel vs layer-by-layer plan replay (code domain) ==")
    for name in ("ecg", "chain"):
        e = mk[name]
        print(f"{e['shape']}: dispatches "
              f"{e['per_layer_dispatches']}->{e['megakernel_dispatches']}, "
              f"per-layer {e['per_layer_us']:.0f}us, "
              f"megakernel {e['megakernel_us']:.0f}us "
              f"({e['speedup']:.2f}x)")
    rw = throughput.rwkv_fused_vs_solo(iters=5)
    print("\n== rwkv r/k/v/g: batch_concat fusion group vs solo ==")
    print(f"{rw['shape']}: dispatches={rw['dispatches']} "
          f"fused {rw['speedup']:.2f}x")
    mo = throughput.moe_prelowered_vs_percall(iters=5)
    print("\n== moe experts: prelowered expert_stack vs per-call ==")
    print(f"{mo['shape']}: prelowered {mo['speedup']:.2f}x")
    pb = throughput.plan_bytes_footprint()
    print("\n== packed plan bytes vs fp32 bake ==")
    for name, e in pb.items():
        print(f"{name}: packed {e['packed_bytes']/1024:.0f}KiB vs "
              f"fp32 {e['fp32_bake_bytes']/1024:.0f}KiB "
              f"({e['reduction']:.1f}x smaller)")
    cs = throughput.serve_cold_start()
    print("\n== serve cold start: lower() vs plan-cache load ==")
    print(f"{cs['shape']}: lower {cs['lower_us']/1e3:.0f}ms, "
          f"cache load {cs['load_us']/1e3:.0f}ms "
          f"({cs['speedup']:.2f}x, {cs['cache_bytes']/1024:.0f}KiB)")
    fc = throughput.fleet_calibration_throughput()
    print("\n== fleet calibration: vmapped vs per-chip loop ==")
    print(f"{fc['shape']}: sequential {fc['sequential_us']/1e3:.0f}ms, "
          f"vmapped {fc['vmapped_us']/1e3:.0f}ms "
          f"({fc['speedup']:.2f}x)")
    fr = throughput.fleet_remap_throughput()
    print("\n== fleet remap: hot-swap vs full re-lower ==")
    print(f"{fr['shape']}: {fr['moved_chunks']} chunk(s) moved, "
          f"remap {fr['remap_us']/1e3:.0f}ms; hot-swap "
          f"{fr['hot_swap_us']/1e3:.1f}ms vs full re-lower "
          f"{fr['full_relower_us']/1e3:.1f}ms ({fr['speedup']:.2f}x)")
    cal = throughput.calibrated_vs_ideal_replay(iters=5)
    print("\n== calibrated-snapshot vs ideal-bake plan replay ==")
    print(f"{cal['shape']}: ideal {cal['ideal_us']:.0f}us, "
          f"calibrated {cal['calibrated_us']:.0f}us "
          f"({cal['speedup']:.2f}x, same executable: "
          f"{cal['same_executable']}; measure+fit once = "
          f"{cal['calibrate_us']/1e3:.0f}ms, "
          f"{cal['measurements']} measurements)")
    # runs LAST among the timed entries: the interpret-mode block kernel
    # perturbs the timings of whatever follows it on shared runners
    ab = throughput.attention_block_megakernel_throughput(iters=5)
    print("\n== attention+MLP block: megakernel vs per-layer fallback ==")
    print(f"{ab['shape']}: dispatches "
          f"{ab['per_layer_dispatches']}->{ab['megakernel_dispatches']} "
          f"(model path {ab['model_path_dispatches']}), "
          f"per-layer {ab['per_layer_us']:.0f}us, "
          f"megakernel {ab['megakernel_us']:.0f}us "
          f"({ab['speedup']:.2f}x; vs model path "
          f"{ab['model_path_speedup']:.2f}x)")
    out = {"plan_vs_percall": pc, "transformer_block": tb,
           "megakernel": mk, "attention_block_megakernel": ab,
           "rwkv_fused_vs_solo": rw,
           "moe_prelowered_vs_percall": mo, "calibrated_replay": cal,
           "fleet_calibration": fc, "fleet_remap": fr,
           "plan_bytes": pb, "serve_cold_start": cs,
           "wall_s": (obs_trace.clock_us() - tr.t0_us) / 1e6}
    with open("BENCH_smoke.json", "w") as f:
        json.dump(out, f, indent=2, default=float)
    obs_trace.end(tr)
    obs_report.dump_run("BENCH_smoke_obs.jsonl", tr,
                        obs_metrics.registry())
    print(f"\nsmoke benchmarks done in {out['wall_s']:.0f}s "
          f"-> BENCH_smoke.json (+ BENCH_smoke_obs.jsonl)")
    # Two gate tiers since the PR-8 chunk-scan kernels: the faithful
    # fused-split path now lax.scans weight chunks, which sped EVERY
    # per-layer jnp dispatch 1.4-1.7x - including the per-call / solo
    # BASELINES of these entries.  Entries whose optimized side still
    # wins outright keep the 1.0x floor; entries comparing two
    # now-equally-fast code paths (plan replay vs percall at small
    # shapes, vmapped group fusion vs independent solo dispatches, the
    # ECG megakernel vs scan-fast per-layer replay) gate PARITY at
    # 0.85x - their structural claims (zero lowering per replay, 4->1 /
    # 3->1 dispatches) are pinned by dispatch/lowering counters in
    # tests, and the timing floor only catches pathological regressions.
    floors = {"plan_vs_percall": (pc["plan_speedup"], 0.85),
              "plan_vs_percall.fused": (pc["fused_speedup"], 1.0),
              "serve_cold_start": (cs["speedup"], 1.0),
              "transformer_block": (tb["plan_speedup"], 0.85),
              "megakernel": (mk["megakernel_speedup"], 1.0),
              "megakernel.ecg": (mk["ecg"]["speedup"], 0.85),
              "attention_block_megakernel": (ab["speedup"], 1.0),
              "rwkv_fused_vs_solo": (rw["speedup"], 0.85),
              "moe_prelowered_vs_percall": (mo["speedup"], 1.0),
              "fleet_calibration": (fc["speedup"], 1.0),
              "fleet_remap": (fr["speedup"], 1.0)}
    # shared runners jitter small-shape timings by +-20%, and a full-suite
    # run perturbs whatever entry follows a heavy one.  A single transient
    # dip is NOT a regression: re-measure a failing entry (alone, up to
    # twice) and gate on its best observation.  A real regression fails
    # all three measurements.
    remeasure = {
        "plan_vs_percall":
            lambda: throughput.plan_vs_percall_throughput(
                iters=5)["plan_speedup"],
        "plan_vs_percall.fused":
            lambda: throughput.plan_vs_percall_throughput(
                iters=5)["fused_speedup"],
        "serve_cold_start":
            lambda: throughput.serve_cold_start()["speedup"],
        "transformer_block":
            lambda: throughput.transformer_block_plan_throughput(
                iters=5)["plan_speedup"],
        "megakernel":
            lambda: throughput.megakernel_vs_per_layer_throughput(
                iters=5)["megakernel_speedup"],
        "megakernel.ecg":
            lambda: throughput.megakernel_vs_per_layer_throughput(
                iters=5)["ecg"]["speedup"],
        "attention_block_megakernel":
            lambda: throughput.attention_block_megakernel_throughput(
                iters=5)["speedup"],
        "rwkv_fused_vs_solo":
            lambda: throughput.rwkv_fused_vs_solo(iters=5)["speedup"],
        "moe_prelowered_vs_percall":
            lambda: throughput.moe_prelowered_vs_percall(
                iters=5)["speedup"],
        "fleet_calibration":
            lambda: throughput.fleet_calibration_throughput()["speedup"],
        "fleet_remap":
            lambda: throughput.fleet_remap_throughput()["speedup"],
    }
    for k, (got, floor) in floors.items():
        for attempt in range(2):
            if got >= floor:
                break
            print(f"gate {k} at {got:.2f}x (floor {floor:.2f}x): "
                  f"re-measuring (attempt {attempt + 1}/2)")
            got = max(got, remeasure[k]())
        floors[k] = (got, floor)
    bad = {k: f"{got:.2f}x < {floor:.2f}x"
           for k, (got, floor) in floors.items() if got < floor}
    if bad:
        print(f"FAIL: replay speedups regressed below their floors: {bad}")
        sys.exit(1)
    # packed-bytes gate: deterministic (pure structure, no timing).  The
    # oracle-fpn ECG entry is reported but ungated - the per-cell oracle
    # gain map has no compressed form (see plan_bytes_footprint); every
    # hardware-representable bake must stay <= 0.3x of the fp32 bake.
    fat = {
        k: pb[k]["ratio"] for k in ("ecg_calibrated", "transformer_block")
        if pb[k]["ratio"] > 0.3
    }
    if fat:
        print(f"FAIL: packed plans exceed 0.3x of the fp32 bake: {fat}")
        sys.exit(1)
    # calibrated-replay gate.  Packed stores (PR 8) make the oracle bake
    # (per-cell gain_map) and a measured bake (per-chunk chunk_gain)
    # structurally different BY DESIGN, so executable identity is now
    # pinned where production needs it: two MEASURED snapshots differ in
    # leaf values only and must share ONE compiled executable
    # (recalibration never recompiles).  The ideal-vs-calibrated timing
    # ratio keeps a coarse floor against gross data-path regressions.
    if not cal["same_executable"] or cal["speedup"] < 0.8:
        print(f"FAIL: calibrated-snapshot replay regressed vs ideal bake: "
              f"same_executable={cal['same_executable']} "
              f"speedup={cal['speedup']:.2f}x")
        sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size ECG training run (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="quick CI subset -> BENCH_smoke.json")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        smoke()
        return

    t0 = time.time()
    from benchmarks import ecg_accuracy, roofline, table1_energy, throughput

    bad = table1_energy.main()
    pc = throughput.main()
    kernels_micro()
    ecg_accuracy.main(fast=not args.full)
    roofline.main()
    with open("BENCH_full.json", "w") as f:
        json.dump({"plan_vs_percall": pc}, f, indent=2, default=float)
    print(f"\nbenchmarks done in {time.time() - t0:.0f}s; "
          f"table1 rows off by >2%: {bad}")
    if bad:
        sys.exit(1)


if __name__ == "__main__":
    main()
