"""Find a cell's configuration, traffic mix, drivers and metric readers by
the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
metric sits in files of its own, so a cell is added by adding files and
entries, never by editing one:

- ``configs/<config>.json``: the configuration as run; its ``kind`` names
  the system driver ``models/<kind>.py`` and the plain reference
  ``reference/<kind>.py``;
- ``traffic/<traffic>.json``: the parameters of a traffic mix, read by
  :mod:`chipbench.traffic`;
- ``metrics/<metric>.py``: one reader per metric, end-to-end or per-layer.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic file's contents
    end_to_end: tuple       # metric entries this cell reports with --trace 0
    per_layer: tuple        # metric entries this cell reports with --trace 1


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell in cells


def find_cell(name: str, bench: dict, root: pathlib.Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)),
    )


def load_module(path: pathlib.Path, name: str):
    """Import a driver, reference or reader by file path (its file name
    may hold dots, as a metric's name does)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    return load_module(BENCH_DIR / "models" / f"{kind}.py",
                       f"chipbench_driver_{kind}")


def reference(kind: str):
    return load_module(BENCH_DIR / "reference" / f"{kind}.py",
                       f"chipbench_reference_{kind}")


def reader(metric: str):
    return load_module(BENCH_DIR / "metrics" / f"{metric}.py",
                       "chipbench_metric_" + metric.replace(".", "_"))


def peaks() -> dict:
    with open(BENCH_DIR / "peaks.json") as f:
        return json.load(f)


def peak(device_kind: str) -> dict:
    """The published peaks of the chip JAX names ``device_kind``.  A kind
    that is not in the table raises: a share of another chip's peak is
    wrong, not approximate."""
    table = peaks()["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
