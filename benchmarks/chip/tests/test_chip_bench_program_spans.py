"""The per-layer metrics read from the program's own spans: each ECG
cell's traced run reports ``preprocess_host_us.<cell>`` from the
program's ``ecg.preprocess_us`` histogram, and a program without that
histogram (or with too few samples) gives no value rather than a wrong
one."""
import json
import types

import pytest

from chipbench_tiny import device, spec, steer

import run as bench_run

SEED = 2**31 + 23          # a seed wider than 32 signed bits
CELLS = {"ecg-stream": "preprocess_host_us.ecg_stream",
         "ecg-holter": "preprocess_host_us.ecg_holter"}


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_preprocess_host_us(name, monkeypatch, capsys,
                                               tmp_path):
    steer(monkeypatch)
    monkeypatch.setattr(device, "CACHE_DIR", tmp_path / "jax")
    assert bench_run.main(["--workload", name, "--seed", str(SEED),
                           "--seconds", "5", "--trace", "1"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True, out
    metric = CELLS[name]
    entry = {m["name"]: m for m in spec.find_cell(
        name, spec.load_benchmark()).per_layer}[metric]
    got = out["metrics"][metric]
    assert got["unit"] == entry["unit"] == "us"
    assert got["value"] > 0


def _run(attempted, failed=0):
    record = types.SimpleNamespace(attempted=attempted, failed=failed)
    return types.SimpleNamespace(record=record)


@pytest.fixture
def registry(monkeypatch):
    """A fresh program registry for the test, the process's own restored
    afterwards."""
    from repro.obs import metrics

    reg = metrics.Registry()
    monkeypatch.setattr(metrics, "_REGISTRY", reg)
    return reg


@pytest.mark.parametrize("metric", CELLS.values())
def test_reader_without_histogram_gives_none(metric, registry):
    assert spec.reader(metric).read(_run(3)) is None


@pytest.mark.parametrize("metric", CELLS.values())
def test_reader_takes_median_of_window_calls(metric, registry):
    hist = registry.histogram("ecg.preprocess_us")
    for v in (90_000.0, 30.0, 10.0, 20.0):     # set-up's warm call first
        hist.record(v)
    reader = spec.reader(metric)
    assert reader.read(_run(3)) == 20.0
    assert reader.read(_run(4, failed=1)) == 20.0   # completed requests
    assert reader.read(_run(5)) is None        # fewer samples than requests
    hist.dropped = 1
    assert reader.read(_run(3)) is None        # samples were lost
