"""The chip benchmark's command on the CPU: each cell's traffic driven in
process at tiny sizes, the shape of the result line, and the refusal to
report without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench_tiny import BENCH, CELLS, device, spec, steer
from chipbench_tiny import cell as tiny_cell

import run as bench_run

ROOT = BENCH.parents[1]
SEED = 2**31 + 11          # a seed wider than 32 signed bits


def _last_line(capsys) -> dict:
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("name", CELLS)
def test_cell_result_line(name, monkeypatch, capsys, tmp_path):
    steer(monkeypatch)
    monkeypatch.setattr(device, "CACHE_DIR", tmp_path / "jax")
    assert bench_run.main(["--workload", name, "--seed", str(SEED),
                           "--seconds", "0.3", "--trace", "0"]) == 0
    out, lines = _last_line(capsys)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out
    assert out["attempted"] > 0 and out["failed"] == 0
    # a cell that BENCHMARK.json lists reports its end-to-end metrics
    bench = spec.load_benchmark()
    listed = name in {w["name"] for w in bench["workloads"]}
    wanted = spec.find_cell(name, bench).end_to_end if listed else ()
    assert set(out["metrics"]) == {m["name"] for m in wanted}
    for m in wanted:
        got = out["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    limits = tiny_cell(name).config["limits"]
    assert set(out["checks"]) == set(limits)
    for k, c in out["checks"].items():
        assert c["limit"] == limits[k]["limit"] and c["value"] <= c["limit"]
    # the window compiled nothing: every shape was warmed in set-up
    assert any(line.startswith("compiles_in_window=0 ") for line in lines)


def test_traced_run_line(monkeypatch, capsys, tmp_path):
    steer(monkeypatch)
    monkeypatch.setattr(device, "CACHE_DIR", tmp_path / "jax")
    assert bench_run.main(["--workload", "ecg-stream", "--seed", "5",
                           "--seconds", "5", "--trace", "1"]) == 0
    out, _ = _last_line(capsys)
    assert out["correct"] is True
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert 0 < out["device"]["window_s"] < 5      # the traffic's 0.3 s
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    per_layer = {m["name"] for m in spec.find_cell(
        "ecg-stream", spec.load_benchmark()).per_layer}
    assert set(out["metrics"]) <= per_layer
    assert not (tmp_path / "trace" / "ecg-stream").exists()   # removed


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "ecg-stream", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_alone_without_the_program_fails(tmp_path):
    """Only BENCHMARK.json and the benchmark's files: no program to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "ecg-stream", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
