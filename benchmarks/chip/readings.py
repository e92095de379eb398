"""Readings that the limits of a cell's comparison are set from.

    python3 benchmarks/chip/readings.py --workload <cell> \
        --seeds <n> [<n> ...] --seconds <s>

For each seed, in one process (set-up is long, and what is compiled once
is reused): build the cell as a run does, serve its traffic for a short
window at the cell's own sizes and load, free the program, then read

- ``program``: the numbers the run's check compares, for what the
  program served;
- ``control``: the same numbers for the control, the plain reference in
  bfloat16 put in the program's place.

One JSON line per seed.  A limit lies above every program reading and
below every control reading (see PERF.md).  The benchmark's own runs do
not run the control.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import device, spec, traffic  # noqa: E402


def read(cell: spec.Cell, seed: int, seconds: float) -> dict:
    devices = device.require(cell.chips)
    device.enable_compile_cache()
    kind = cell.config["kind"]
    driver, reference = spec.driver(kind), spec.reference(kind)
    seeds = device.Seeds(seed)
    system = driver.build(cell.config, cell.traffic, seeds,
                          spec.peak(devices[0].device_kind))
    schedule = traffic.Schedule(cell.traffic, seeds.rng(100))
    rec = traffic.run_window(system, cell.traffic, schedule, seconds)
    system.free_program()
    program = reference.check(system, cell.config, cell.traffic,
                              seeds.rng(200))
    control = reference.control(system, cell.config, cell.traffic,
                                seeds.rng(200))
    return {"workload": cell.name, "seed": seed, "attempted": rec.attempted,
            "program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload, spec.load_benchmark())
    try:
        for seed in args.seeds:
            print(json.dumps(read(cell, seed, args.seconds)), flush=True)
    except device.NoChip as e:
        device.log(f"readings: {e}; nothing was run")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
