"""Tiny versions of the benchmark's cells for CPU tests: the same drivers,
references and harness, with the LM cut to toy widths and the ECG
traffic to a few windows, the device check and the peak table steered
to the CPU."""
from __future__ import annotations

import dataclasses
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
for p in (str(BENCH), str(BENCH.parents[1] / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import device, spec  # noqa: E402

CPU_PEAK = {"int8_ops_per_s": 393e12, "bf16_flops_per_s": 197e12,
            "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

TINY_LM = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=2, vocab_size=256)


def tiny(cell: spec.Cell) -> spec.Cell:
    cfg, tr = dict(cell.config), dict(cell.traffic)
    if cfg["kind"] == "lm":
        cfg.update(TINY_LM)
        p = min(tr["request"]["prompt_tokens"], 8)
        t = min(tr["request"]["new_tokens"], 4)
        tr.update(request={"prompt_tokens": p, "new_tokens": t},
                  max_len=p + t, check={"batches": 2}, trace_seconds=0.3)
    else:
        windows = tr["request"]["windows"]
        tr.update(request={"windows": 1 if windows == 1 else 12},
                  pool_windows=8, recordings=2, trace_seconds=0.3)
    return dataclasses.replace(cell, config=cfg, traffic=tr)


def steer(monkeypatch):
    """Run the harness on the CPU: the devices JAX has, the test's peaks,
    no persistent compile cache, tiny cells (those BENCHMARK.json lists,
    and the others the tests drive)."""
    import jax

    find = spec.find_cell

    def find_tiny(name, bench):
        if name in {w["name"] for w in bench["workloads"]}:
            return tiny(find(name, bench))
        return cell(name)

    monkeypatch.setattr(device, "require", lambda n: jax.devices()[:n])
    monkeypatch.setattr(spec, "peak", lambda kind: CPU_PEAK)
    monkeypatch.setattr(device, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(spec, "find_cell", find_tiny)


# the configuration and traffic of each cell the tests drive, so that a
# test of a driver does not depend on which cells BENCHMARK.json lists
CELLS = {"ecg-stream": ("ecg-bss2", "stream"),
         "ecg-holter": ("ecg-bss2", "holter"),
         "lm-decode": ("stablelm-3b-4l", "decode"),
         "lm-prefill": ("stablelm-3b-4l", "prefill")}


def cell(name: str) -> spec.Cell:
    """A tiny cell from its configuration and traffic files."""
    config, mix = CELLS[name]
    with open(BENCH / "configs" / f"{config}.json") as f:
        cfg = json.load(f)
    with open(BENCH / "traffic" / f"{mix}.json") as f:
        traffic = json.load(f)
    return tiny(spec.Cell(name=name, chips=1, config=cfg, traffic=traffic,
                          end_to_end=(), per_layer=()))
