"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell
asks for.  The run makes the cell's weights and traffic from ``--seed``,
builds the program and warms up every shape the traffic uses (set-up),
then measures for ``--seconds`` seconds.  With ``--trace 1`` it measures
the traffic mix's ``trace_seconds`` under the profiler instead and
reports the cell's per-layer metrics from the trace.  After the window it
frees the program, runs the plain reference over what the window served
and compares.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), then ``checks``: each number compared with its
limit.  The same numbers end standard error.  Without a TPU, or with
fewer chips than the cell asks for, the run prints no result and exits
with 1.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import time
import types

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import device, spec, tracing, traffic  # noqa: E402


def measure(cell: spec.Cell, seed: int, seconds: float,
            trace: bool) -> dict:
    """One run of ``cell``; returns the result line's object."""
    devices = device.require(cell.chips)
    import repro  # noqa: F401  (the program under test must be present)

    device.enable_compile_cache()
    kind = cell.config["kind"]
    driver, reference = spec.driver(kind), spec.reference(kind)
    seeds = device.Seeds(seed)
    peak = spec.peak(devices[0].device_kind)

    t0 = time.perf_counter()
    system = driver.build(cell.config, cell.traffic, seeds, peak)
    setup_s = time.perf_counter() - t0
    device.log(f"setup_s={setup_s}")

    window = seconds
    trace_dir = None
    if trace:
        window = min(seconds, float(cell.traffic.get("trace_seconds",
                                                     seconds)))
        trace_dir = device.CACHE_DIR.parent / "trace" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
    schedule = traffic.Schedule(cell.traffic, seeds.rng(100))
    system.annotate = trace
    with device.CompileCounter() as compiles:
        with tracing.capture(trace_dir):
            rec = traffic.run_window(system, cell.traffic, schedule, window,
                                     annotate=trace)
    print(f"compiles_in_window={compiles.count} calls={rec.calls} "
          f"attempted={rec.attempted} units={rec.units} "
          f"elapsed_s={rec.elapsed_s} late_s={rec.late_s}", flush=True)
    memory_peak = device.memory_peak_bytes(devices)
    dev = device.describe(devices, memory_peak)

    summary = None
    if trace:
        summary = tracing.summarize(trace_dir, window)
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s

    # what a metric reader reads
    run = types.SimpleNamespace(cell=cell, record=rec, setup_s=setup_s,
                                system=system, trace=summary, peak=peak)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = spec.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    system.free_program()
    numbers = reference.check(system, cell.config, cell.traffic,
                              seeds.rng(200))
    limits = cell.config["limits"]
    checks = {k: {"value": v, "limit": limits[k]["limit"]}
              for k, v in numbers.items() if k in limits}
    correct = (rec.failed == 0 and rec.attempted > 0
               and compiles.count == 0
               and len(checks) == len(limits)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    for k, c in checks.items():
        device.log(f"check {k}: {c['value']} (limit {c['limit']})")
    result = {"correct": correct, "attempted": rec.attempted,
              "failed": rec.failed, "metrics": metrics, "device": dev}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.find_cell(args.workload, spec.load_benchmark())
        result = measure(cell, args.seed, args.seconds, bool(args.trace))
    except device.NoChip as e:
        device.log(f"run: {e}; nothing was run")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
