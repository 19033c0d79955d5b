"""Finding a cell's pieces by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the model's sizes as run, its source and
  the name of its plain reference, ``bench/reference/<reference>.py``;
* ``bench/traffic/<traffic>.json``: the traffic mix; its ``kind`` names the
  runner, ``bench/kinds/<kind>.py``;
* ``bench/workloads/<cell>.json``: the cell's program settings and the
  limits of its correctness check;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
* ``bench/work/<kernel>.py``: the operations and bytes of one kernel.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_spec(cell: str, bench: dict | None = None) -> dict:
    """Everything one cell needs, by name: its entry in BENCHMARK.json,
    configuration, traffic, workload file and the metrics it reports."""
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise KeyError(f"no cell named {cell!r} in BENCHMARK.json")

    def applies(metric):
        return cell in metric.get("workloads", [cell])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in moved
                 and cell in m.get("workloads", [cell])]
    return {
        "cell": cell,
        "entry": entry,
        "config": load_json(BENCH_DIR / "configs" / f"{entry['config']}.json"),
        "traffic": load_json(BENCH_DIR / "traffic"
                             / f"{entry['traffic']}.json"),
        "workload": load_json(BENCH_DIR / "workloads" / f"{cell}.json"),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def _load_file(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The module ``bench/metrics/<name>.py``: LAYER, UNIT, BETTER, MOVES,
    SOURCE and ``read(ctx) -> float | None``."""
    return _load_file(BENCH_DIR / "metrics" / f"{name}.py",
                      f"bench_metric_{name.replace('.', '_')}")


def work(kernel: str):
    """The module ``bench/work/<kernel>.py`` of operation and byte counts."""
    return _load_file(BENCH_DIR / "work" / f"{kernel}.py",
                      f"bench_work_{kernel.replace('.', '_')}")


def reference(name: str):
    return importlib.import_module(f"bench.reference.{name}")


def runner(kind: str):
    return importlib.import_module(f"bench.kinds.{kind}")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown device is an error."""
    table = load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]
