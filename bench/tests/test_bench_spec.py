"""Every cell of BENCHMARK.json resolves its configuration, traffic,
check and metric files by name, and the file keeps to the benchmark's
contract on names, keys and bounds. Each check is a function of the
benchmark's description and a cell's name, so that a copy with a cell
appended as data alone goes through the same checks."""
import copy
import json
import re
import shutil

import pytest

from bench import spec, traffic
from bench.tests.test_bench_mesh import check_cell_mesh

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def check_cell_files(bench, name, data_dir=spec.BENCH):
    cell = spec.Cell(bench, name, data_dir=data_dir)
    assert cell.config["name"] == cell.workload["config"]
    assert float(cell.check["logit_gap_limit"]) > 0
    mix = cell.traffic
    assert mix["prompt"]["max"] <= mix["prefill_len"] <= mix["max_len"]
    assert mix["prompt"]["max"] + mix["output"]["max"] <= mix["max_len"]
    for trace in (False, True):
        for m in cell.metrics(trace):
            assert callable(spec.load_reader(m["name"]))
    spec.load_reference(cell.config["reference"])


def check_cell_metrics(bench, name, data_dir=spec.BENCH):
    cell = spec.Cell(bench, name, data_dir=data_dir)
    e2e = {m["name"] for m in cell.metrics(False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.metrics(True)
    assert layer
    for m in layer:     # each moves an end-to-end metric the cell reports
        assert m["moves"] in e2e


def check_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert json.load(open(spec.ROOT / c["file"]))["reduced"] \
            == c["reduced"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    # at most half the cells, rounded down, take four chips; one always may
    wide = sum(w["chips"] == 4 for w in bench["workloads"])
    assert wide <= max(1, len(bench["workloads"]) // 2)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in bench["per_layer"]}
    assert all(len(x) <= 200 for x in layers)


def check_cell_fits(bench, name, data_dir=spec.BENCH):
    # 2 + 14 runs per cell, each run_seconds + 60 s, 2 x 90 s of compile
    # per cell and 1200 s spare within 43200 s, at 24 cells
    rs = bench["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    mix = spec.Cell(bench, name, data_dir=data_dir).traffic
    assert traffic.request_count(mix, rs) > 10


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_its_files(name):
    check_cell_files(BENCH, name)


@pytest.mark.parametrize("name", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_layer(name):
    check_cell_metrics(BENCH, name)


def test_names_keys_and_bounds_keep_to_the_contract():
    check_contract(BENCH)


@pytest.mark.parametrize("name", CELLS)
def test_a_full_check_fits_its_time_with_every_cell(name):
    check_cell_fits(BENCH, name)


def test_a_four_chip_cell_joins_as_data_alone(tmp_path):
    # a later PR adds a four-chip cell by entries and files of its own:
    # an existing configuration and traffic, a check file named after
    # the cell, and an existing per-layer metric that lists it
    bench = copy.deepcopy(BENCH)
    name = "four_chip_data_alone_probe"
    bench["workloads"].append({
        "name": name, "config": "starcoder2_15b_pp4", "traffic": "code_repo",
        "chips": 4, "why": "a cell appended as data alone, on four chips"})
    layer = next(m for m in bench["per_layer"]
                 if m["name"] == "hadamard.prefill.roofline_pct")
    layer["workloads"].append(name)
    data = tmp_path / "bench"
    for d in ("traffic", "cells"):
        shutil.copytree(spec.BENCH / d, data / d)
    shutil.copy(data / "cells" / "sc2_code.json",
                data / "cells" / f"{name}.json")
    check_contract(bench)
    for w in bench["workloads"]:
        for check in (check_cell_files, check_cell_metrics, check_cell_fits,
                      check_cell_mesh):
            check(bench, w["name"], data)
