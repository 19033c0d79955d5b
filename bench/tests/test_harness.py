"""The harness takes its cells, configurations and metrics as data: every
entry of BENCHMARK.json is found by name among the files under bench/, and
every file there is named in BENCHMARK.json."""
import json
import os
import re

import pytest

from bench import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = common.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _stems(sub, ext):
    d = common.BENCH_DIR / sub
    return sorted(f[:-len(ext)] for f in os.listdir(d) if f.endswith(ext))


def test_top_level_keys():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


def test_every_file_is_named_and_every_name_has_a_file():
    assert _stems("configs", ".json") == sorted(
        c["name"] for c in BENCH["configs"])
    assert _stems("workloads", ".json") == sorted(CELLS)
    assert _stems("traffic", ".json") == sorted(
        {w["traffic"] for w in BENCH["workloads"]})
    assert _stems("metrics", ".py") == sorted(
        m["name"] for m in BENCH["per_layer"])
    for c in BENCH["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"


def test_every_config_keeps_a_cell():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_config_files_state_their_cuts():
    for c in BENCH["configs"]:
        f = common.load_json(common.ROOT / c["file"])
        assert f["name"] == c["name"]
        assert f["reduced"] == c["reduced"]
        assert set(c["reduced"]) <= set(f["model"])
        assert set(c["reduced"]) == set(f.get("changed_from_source", {}))
        assert (common.BENCH_DIR / "reference"
                / f"{f['reference']}.py").exists()


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_declares_itself(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    mod = common.metric_reader(name)
    assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.MOVES, mod.SOURCE) == (
        entry["layer"], entry["unit"], entry["better"], entry["moves"],
        entry["source"])
    assert callable(mod.read)


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_metric_moves_a_metric_its_cells_report(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    for cell in entry.get("workloads", CELLS):
        spec = common.cell_spec(cell, BENCH)
        assert entry["moves"] in {m["name"] for m in spec["end_to_end"]}
        assert name in {m["name"] for m in spec["per_layer"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_found_by_name(cell):
    spec = common.cell_spec(cell, BENCH)
    assert common.runner(spec["traffic"]["kind"]).run
    names = {m["name"] for m in spec["end_to_end"]}
    assert {"setup_s"} < names
    assert spec["per_layer"]
    limits = spec["workload"]["limits"]
    assert all(v > 0 for v in limits.values())


def test_layers_named_alike():
    layers = {}
    for m in BENCH["per_layer"]:
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        layers.setdefault(m["layer"], []).append(m["name"])
    assert layers


def test_kernel_rooflines_are_named_for_their_kernel():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"


def test_file_is_small():
    assert len(json.dumps(BENCH)) < 64 * 1024
